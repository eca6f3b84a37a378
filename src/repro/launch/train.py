"""End-to-end training launcher.

Examples:
  # ~100M-param vertical-split LM for a few hundred steps (deliverable b):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \\
      --scale 100m --steps 300 --batch 8 --seq 256

  # any assigned arch, reduced, quick sanity:
  PYTHONPATH=src python -m repro.launch.train --arch zamba2-7b --reduced \\
      --steps 20 --batch 2 --seq 64

  # centralized baseline (paper Table 2 comparison):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --scale 100m \\
      --vertical off --steps 300

  # pipelined split-training runtime: 4 microbatches, simulated federation
  # clock in the summary (see repro.runtime for the execution model):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 20 --runtime pipelined --microbatches 4

  # bounded-staleness no-wait mode with a 10x straggler on client 1:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 20 --runtime nowait --microbatches 4 --straggler 1

  # SPLIT EXECUTION over real per-role processes: spawn one OS process per
  # feature holder (each owns only its tower + embedding slice and its own
  # token stream), train through the Executor over TCP loopback sockets,
  # and verify step-0 gradients against the serial protocol_step:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 5 --transport multiproc

  # same, threads instead of processes, pipelined with adaptive no-wait
  # deadlines and a wall-clock straggler on client 1:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 20 --transport inproc --runtime nowait --microbatches 4 \\
      --straggler 1

  # cross-step pipelined split execution: keep 2 steps in flight so step
  # t+1 tower forwards overlap step t's server backward + jacobian drain
  # (towers train on delayed gradients, one update behind):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 20 --transport inproc --inflight-steps 2

  # SECURE AGGREGATION over real processes: one-time in-protocol key
  # exchange, then every worker masks its cut uplink at the source
  # (Bonawitz-style pairwise masks, repro.core.secure_agg) so role 0 only
  # ever observes the aggregate; step 0 verifies the masked merge against
  # the unmasked serial protocol_step:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 5 --batch 4 --seq 64 --transport multiproc --secure-agg

  # COMPRESSED cut traffic on the wire (repro.core.compression): workers
  # top-k-sparsify (or int8-quantize) their cut uplinks at the source with
  # error feedback, role 0 compresses the jacobian downlinks symmetrically,
  # the ledger audits codec wire bytes, and step 0 verifies against the
  # serial protocol_step running the same codec:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 5 --batch 4 --seq 64 --transport multiproc \\
      --compress topk --topk-fraction 0.25

  # HIERARCHICAL AGGREGATION (repro.runtime.topology): overlay a fanout-2
  # tree on the federation — relay workers partial-sum their subtree's cut
  # uplinks and role 0 merges/fans-out only min(F, K) frames per
  # microbatch instead of K (composes with --secure-agg; step 0 verifies
  # the reassociated f32 merge to TREE_VERIFY_ATOL):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 5 --batch 4 --seq 64 --clients 8 --transport inproc \\
      --runtime pipelined --microbatches 2 --agg-tree-fanout 2

  # split execution is family-agnostic (repro.models.split_program): moe
  # ships its router aux loss through the protocol's role-0 -> role-3 aux
  # slot, audio trains mel-band encoder towers, vlm by-source modality
  # towers — any vertical config over any transport:
  PYTHONPATH=src python -m repro.launch.train --arch deepseek-moe-16b \\
      --reduced --steps 5 --batch 4 --seq 64 --transport inproc
  PYTHONPATH=src python -m repro.launch.train --arch whisper-tiny \\
      --reduced --steps 5 --batch 4 --seq 64 --transport multiproc
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp

from repro.configs.base import VerticalConfig, get_arch
from repro.core import compat
from repro.data.loader import LMBatchLoader
from repro.train.loop import train


def scale_config(cfg, scale: str):
    """Budget presets: shrink depth/width, keep the family + technique."""
    if scale == "full":
        return cfg
    presets = {
        # ~100M params with the smollm tokenizer (embed ~38M + 12 layers)
        "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                     d_ff=2048),
        "25m": dict(num_layers=6, d_model=384, num_heads=6, num_kv_heads=2,
                    d_ff=1024),
        "10m": dict(num_layers=4, d_model=256, num_heads=4, num_kv_heads=2,
                    d_ff=512),
    }
    if scale not in presets:
        raise SystemExit(f"unknown --scale {scale}")
    fields = dict(presets[scale])
    if cfg.family == "ssm":
        # pure Mamba: no attention heads, and the FFN lives inside the SSD
        # block so the preset d_ff is meaningless too
        for f in ("num_heads", "num_kv_heads", "d_ff"):
            fields.pop(f)
    elif cfg.family == "hybrid":
        # zamba2-style: the shared attention block derives its head layout
        # from the arch config, but its FFN width IS the preset d_ff
        for f in ("num_heads", "num_kv_heads"):
            fields.pop(f)
    return dataclasses.replace(cfg, **fields)


def _runtime_report(cfg, args) -> dict:
    """Clock one training step of the chosen --runtime schedule on the
    default federation link model (repro.runtime); pure simulation, the
    jitted train loop above is unaffected."""
    from repro.runtime import (LinkModel, plan_from_arch, simulate_pipelined,
                               simulate_serial)

    M = args.microbatches if args.runtime != "serial" else 1
    W = args.inflight_steps
    plan = plan_from_arch(cfg, args.batch, args.seq, M)
    link = LinkModel.uniform(cfg.vertical.num_clients)
    if args.straggler is not None:
        link = link.with_straggler(args.straggler, slowdown=10.0)
    serial_s = simulate_serial(plan, link).step_time_s
    if args.runtime == "serial" and W == 1:
        report = {"mode": "serial", "step_time_s": serial_s}
    else:
        sim_mode = "pipelined" if args.runtime == "serial" else args.runtime
        sim = simulate_pipelined(plan, link, mode=sim_mode,
                                 steps=1 if W == 1 else 2 * W, cross_step=W)
        report = {
            "mode": sim.mode,
            "step_time_s": sim.step_time_s,
            "speedup_vs_serial": serial_s / sim.step_time_s,
            "microbatches": sim.microbatches,
            "inflight_steps": W,
            # SimReport totals cover all sim.steps simulated steps; report
            # per-step figures so W settings stay comparable to each other
            # and to the measured per-step ExecReport
            "sim_steps": sim.steps,
            "deadline_misses_per_step": sim.total_misses / sim.steps,
            "cut_bytes_per_client": sim.cut_bytes_per_client // sim.steps,
        }
    # runtime-aware placement: where the sweep would put the cut for this
    # schedule (costs.advise_arch_split_depth over plan_from_arch)
    if cfg.num_layers > 1:
        from repro.core.costs import advise_arch_split_depth

        # match the clock reported above: a cross-step window makes even a
        # --runtime serial schedule an overlapped (pipelined) one
        advise = advise_arch_split_depth(
            cfg, batch_size=args.batch, seq_len=args.seq,
            objective="serial" if (args.runtime == "serial" and W == 1)
            else "pipelined",
            microbatches=M, cross_step=W)
        report["advised_tower_layers"] = advise["recommended_tower_layers"]
        report["configured_tower_layers"] = cfg.vertical.tower_layers
    print(f"runtime[{args.runtime}] simulated step "
          f"{report['step_time_s']*1e3:.2f} ms"
          + (f" ({report['speedup_vs_serial']:.2f}x vs serial)"
             if "speedup_vs_serial" in report else "")
          + (f"  advised tower_layers={report['advised_tower_layers']}"
             if "advised_tower_layers" in report else ""))
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--scale", default="full",
                    choices=["full", "100m", "25m", "10m"])
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test reduced variant")
    ap.add_argument("--vertical", default="on", choices=["on", "off"])
    ap.add_argument("--merge", default=None,
                    help="override the cut-layer merge strategy")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--json", default=None, help="write metrics json here")
    ap.add_argument("--runtime", default="serial",
                    choices=["serial", "pipelined", "nowait"],
                    help="split-training schedule to clock (repro.runtime)")
    ap.add_argument("--microbatches", type=int, default=4,
                    help="pipeline depth for --runtime pipelined/nowait")
    ap.add_argument("--inflight-steps", type=int, default=1,
                    help="cross-step window W: submit step t+1 tower "
                         "forwards while step t's server backward/jacobian "
                         "drain is in flight (W>1 trains towers on delayed "
                         "gradients, one update behind; W=1 is the exact "
                         "per-step barrier)")
    ap.add_argument("--straggler", type=int, default=None,
                    help="degrade this client 10x in the runtime simulation "
                         "(real wall-clock delay under --transport "
                         "inproc/multiproc)")
    ap.add_argument("--transport", default="sim",
                    choices=["sim", "inproc", "multiproc"],
                    help="sim: monolithic jitted step + simulated federation "
                         "clock; inproc/multiproc: SPLIT EXECUTION through "
                         "the Executor over per-role threads/processes "
                         "(repro.transport)")
    ap.add_argument("--secure-agg", action="store_true",
                    help="Bonawitz-style secure aggregation: in-protocol "
                         "pairwise key exchange, cut uplinks masked at the "
                         "source, role 0 merges masked cuts and never "
                         "observes a raw activation (sum/avg merges, "
                         "barrier runtimes, split execution only)")
    ap.add_argument("--compress", default=None, choices=["topk", "int8"],
                    help="compress cut traffic on the wire "
                         "(repro.core.compression): workers compress cut "
                         "uplinks at the source with error feedback, the "
                         "executor compresses jacobian downlinks "
                         "symmetrically; step 0 verifies against the serial "
                         "protocol_step running the same codec.  Mutually "
                         "exclusive with --secure-agg")
    ap.add_argument("--topk-fraction", type=float, default=0.25,
                    help="fraction of cut entries kept per vector under "
                         "--compress topk")
    ap.add_argument("--agg-tree-fanout", type=int, default=None,
                    help="overlay a fanout-F aggregation tree on split "
                         "execution (repro.runtime.topology): relay workers "
                         "partial-sum their subtree's cut uplinks so role 0 "
                         "merges/fans-out min(F, K) frames per microbatch "
                         "instead of K.  Additive merges (sum/avg) only; "
                         "composes with --secure-agg, mutually exclusive "
                         "with --compress and --runtime nowait")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = scale_config(cfg, args.scale)
    if args.vertical == "off":
        cfg = cfg.with_vertical(None)
    elif args.merge or args.clients:
        v = cfg.vertical or VerticalConfig()
        v = dataclasses.replace(
            v,
            merge=args.merge or v.merge,
            num_clients=args.clients or v.num_clients,
        )
        cfg = cfg.with_vertical(v)

    if cfg.vertical is None and (args.runtime != "serial"
                                 or args.straggler is not None
                                 or args.transport != "sim"
                                 or args.secure_agg
                                 or args.compress):
        raise SystemExit(
            f"--runtime {args.runtime}/--straggler/--transport/--secure-agg/"
            "--compress need a vertical config; this run is centralized "
            "(--vertical off or arch without one)"
        )
    # every unsound flag composition rejects through the ONE compat matrix,
    # phrased flag-first by compat.cli_reject; per-flag validation (ranges,
    # transports) stays below
    try:
        compat.check(
            "launch", secure=args.secure_agg, compress=args.compress or None,
            tree=args.agg_tree_fanout, nowait=args.runtime == "nowait",
            merge=cfg.vertical.merge if cfg.vertical is not None else None)
    except compat.CompatError as e:
        raise compat.cli_reject(e) from None
    if args.compress:
        if not (0.0 < args.topk_fraction <= 1.0):
            raise SystemExit(
                f"--topk-fraction must be in (0, 1], got {args.topk_fraction}")
        cfg = cfg.with_vertical(dataclasses.replace(
            cfg.vertical, compression=args.compress,
            topk_fraction=args.topk_fraction))
    if args.secure_agg:
        if args.transport == "sim":
            raise SystemExit(
                "--secure-agg needs split execution (--transport "
                "inproc/multiproc): the sim path runs the monolithic "
                "jitted step, there is no uplink to mask")
        try:
            cfg = cfg.with_vertical(dataclasses.replace(
                cfg.vertical, secure_aggregation=True))
        except ValueError as e:  # non-additive merge rejected by the config
            raise SystemExit(f"--secure-agg: {e}")
    if args.agg_tree_fanout is not None:
        if args.transport == "sim":
            raise SystemExit(
                "--agg-tree-fanout needs split execution (--transport "
                "inproc/multiproc): the sim path runs the monolithic jitted "
                "step, there are no relay workers to aggregate at")
        if args.agg_tree_fanout < 2:
            raise SystemExit(
                f"--agg-tree-fanout must be >= 2, got {args.agg_tree_fanout} "
                "(fanout 1 is a chain — every hop still serializes and role "
                "0 gains nothing)")
    if args.transport != "sim":
        # every family has a registered SplitProgram — this only rejects a
        # config with no vertical section (checked above) or an unknown
        # family string
        from repro.models.split_program import get_program

        get_program(cfg)
        if args.checkpoint:
            raise SystemExit("--checkpoint is not supported with split "
                             "execution (tower params live at the clients)")
    if cfg.vertical is not None:
        # fail fast — the runtime report renders after training finishes
        if args.microbatches < 1:
            raise SystemExit(f"--microbatches must be >= 1, got {args.microbatches}")
        if args.inflight_steps < 1:
            raise SystemExit(
                f"--inflight-steps must be >= 1, got {args.inflight_steps}")
        if args.runtime != "serial" and args.batch % args.microbatches:
            raise SystemExit(
                f"--batch {args.batch} not divisible by "
                f"--microbatches {args.microbatches}"
            )
        if args.straggler is not None and not (
                0 <= args.straggler < cfg.vertical.num_clients):
            raise SystemExit(
                f"--straggler {args.straggler} out of range for "
                f"{cfg.vertical.num_clients} clients"
            )

    from repro.launch.compile_cache import setup_compile_cache
    from repro.models.backbone import param_count

    setup_compile_cache()
    n_params = param_count(cfg)
    print(f"arch={cfg.name} family={cfg.family} params={n_params/1e6:.1f}M "
          f"vertical={cfg.vertical}")
    loader = LMBatchLoader(cfg, args.batch, args.seq, seed=args.seed)
    if args.transport != "sim":
        from repro.train.loop import train_split

        _, metrics, report = train_split(
            cfg, loader, steps=args.steps, batch=args.batch, seq=args.seq,
            transport=args.transport, runtime=args.runtime,
            microbatches=args.microbatches,
            inflight_steps=args.inflight_steps, learning_rate=args.lr,
            seed=args.seed, straggler=args.straggler,
            agg_tree_fanout=args.agg_tree_fanout,
        )
        summary = metrics.summary()
        summary.update(arch=cfg.name, params=n_params, steps=args.steps,
                       vertical=args.vertical, transport=args.transport,
                       inflight_steps=args.inflight_steps,
                       secure_agg=args.secure_agg, compress=args.compress,
                       agg_tree_fanout=args.agg_tree_fanout)
        if report is not None:
            summary["runtime"] = {
                "mode": args.runtime,
                "transport": args.transport,
                "step_time_s": report.step_time_s,
                "staleness": getattr(report, "staleness", 0),
                # where each side really computed: multiproc towers run on
                # the host CPU even when role 0 holds an accelerator
                "role0_platform": jax.default_backend(),
                "tower_platform": report.tower_platform,
                "deadline_misses": report.total_misses,
                "cut_bytes_per_client": report.cut_bytes_per_client,
            }
        print(json.dumps(summary, indent=1))
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"summary": summary, "losses": metrics.losses}, f)
        return 0

    params, metrics = train(
        cfg, loader, steps=args.steps, learning_rate=args.lr,
        checkpoint_path=args.checkpoint, seed=args.seed,
    )
    summary = metrics.summary()
    summary.update(arch=cfg.name, params=n_params, steps=args.steps,
                   vertical=args.vertical)
    if cfg.vertical is not None:
        summary["runtime"] = _runtime_report(cfg, args)
    print(json.dumps(summary, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"summary": summary, "losses": metrics.losses}, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
