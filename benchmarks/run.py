"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus the table payloads.

  PYTHONPATH=src python -m benchmarks.run            # quick mode (~2 min)
  PYTHONPATH=src python -m benchmarks.run --full     # full tables (EXPERIMENTS.md)
  PYTHONPATH=src python -m benchmarks.run --roofline dryrun_single.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")


def _check_bench_json(path: str) -> None:
    """Validate a written perf artifact against the committed contract
    (benchmarks/bench_schema.json) — the CI gate that keeps the tracked
    trajectory's shape stable across PRs.  Raises SystemExit on drift."""
    import os

    import jsonschema

    if not os.path.exists(path):
        raise SystemExit(
            f"--check: {path} does not exist — run the benchmarks first "
            "(e.g. python -m benchmarks.run --only split_exec)")
    schema_path = os.path.join(os.path.dirname(__file__),
                               "bench_schema.json")
    with open(schema_path) as f:
        schema = json.load(f)
    with open(path) as f:
        artifact = json.load(f)
    try:
        jsonschema.validate(artifact, schema)
    except jsonschema.ValidationError as e:
        loc = "/".join(str(p) for p in e.absolute_path) or "<root>"
        raise SystemExit(
            f"--check: {path} violates bench_schema.json at {loc}: "
            f"{e.message}")
    sections = {k: len(v) for k, v in artifact.items()}
    print(f"{path} conforms to bench_schema.json ({sections})")


def bench_kernels() -> None:
    """Microbenchmarks of the kernel oracles (CPU host timings)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 256, 1024))
    for strategy in ("max", "avg", "sum", "mul"):
        f = jax.jit(lambda t: ops.merge_pool(t, strategy=strategy))
        f(x).block_until_ready()
        t0 = time.time()
        for _ in range(20):
            out = f(x)
        out.block_until_ready()
        _emit(f"merge_pool/{strategy}", (time.time() - t0) / 20 * 1e6,
              "K=4 B=256 D=1024")

    q = jax.random.normal(key, (1, 4, 512, 64))
    f = jax.jit(lambda a: ops.flash_attention(a, a, a, causal=True))
    f(q).block_until_ready()
    t0 = time.time()
    for _ in range(5):
        out = f(q)
    out.block_until_ready()
    _emit("flash_attention/ref", (time.time() - t0) / 5 * 1e6, "B1 H4 S512 D64")


def bench_runtime(out: dict) -> None:
    """Simulated step time + cut-layer traffic: serial vs pipelined vs
    no-wait (repro.runtime) at K in {2, 4, 8} clients, M=4 microbatches.
    The no-wait row carries a 10x straggler on the last client — the
    scenario bounded staleness exists for."""
    from repro.configs.vertical_mlp import MLPSplitConfig
    from repro.runtime import (LinkModel, plan_step, simulate_pipelined,
                               simulate_serial)

    rows = []
    for K in (2, 4, 8):
        cfg = MLPSplitConfig(
            name=f"runtime_bench_k{K}", input_dim=64 * K, num_classes=2,
            num_clients=K, client_feature_sizes=(64,) * K,
            tower_hidden=(128,), cut_dim=64, server_hidden=(128,), merge="avg",
        )
        plan = plan_step(cfg, batch_size=256, microbatches=4)
        link = LinkModel.uniform(K)
        straggled = link.with_straggler(K - 1, slowdown=10.0)

        serial = simulate_serial(plan, link)
        pipelined = simulate_pipelined(plan, link, mode="pipelined")
        nowait = simulate_pipelined(plan, straggled, mode="nowait")
        # each speedup divides by the serial schedule ON THE SAME LINK
        # model; the straggled-serial baseline is emitted as its own row so
        # the nowait denominator is visible in the table
        serial_straggled = simulate_serial(plan, straggled)
        serial_straggled.mode = "serial_straggled"
        for rep, baseline in ((serial, serial),
                              (serial_straggled, serial_straggled),
                              (pipelined, serial),
                              (nowait, serial_straggled)):
            rows.append({
                "clients": K,
                "mode": rep.mode,
                "step_time_ms": rep.step_time_s * 1e3,
                "speedup_vs_serial": baseline.step_time_s / rep.step_time_s,
                "cut_bytes_per_client": rep.cut_bytes_per_client,
                "deadline_misses": rep.total_misses,
            })
            _emit(f"runtime/{rep.mode}_k{K}", rep.step_time_s * 1e6,
                  f"M=4 {baseline.step_time_s / rep.step_time_s:.2f}x_vs_serial")
    out["runtime"] = rows


def bench_transport(out: dict) -> None:
    """REAL execution (not the simulated clock): one protocol step through
    the Executor over the inline SimTransport vs threaded InprocTransport,
    K in {2, 4}, M=4 microbatches.  Measures the schedule-execution
    machinery itself — tower forwards overlapping the role-0 merge/backward
    on worker threads vs strictly inline."""
    import jax
    import jax.numpy as jnp

    from repro.configs.vertical_mlp import MLPSplitConfig
    from repro.core import split_model, towers
    from repro.runtime.executor import Executor
    from repro.transport import InprocTransport, SimTransport, TowerWorker

    rows = []
    for K in (2, 4):
        cfg = MLPSplitConfig(
            name=f"transport_bench_k{K}", input_dim=64 * K, num_classes=2,
            num_clients=K, client_feature_sizes=(64,) * K,
            tower_hidden=(256,), cut_dim=128, server_hidden=(256,),
            merge="avg",
        )
        params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        B = 256
        x = jax.random.normal(ks[0], (B, cfg.input_dim))
        y = jax.random.randint(ks[1], (B,), 0, cfg.num_classes)
        slices = split_model.feature_slices(cfg)
        feats = [x[:, jnp.asarray(s.indices)] for s in slices]

        def loss_fn(logits, labels):
            return split_model.softmax_xent(logits, labels, cfg.num_classes)

        for name, make in (("sim", SimTransport), ("inproc", InprocTransport)):
            workers = [
                TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
                for k in range(K)
            ]
            tr = make(workers)
            try:
                executor = Executor(tr, towers.mlp_tower_apply, loss_fn,
                                    cfg.merge,
                                    microbatches=4)
                executor.run_step(params["server"], y, features=feats)  # warm
                t0 = time.time()
                reps = 5
                for step in range(1, reps + 1):
                    res = executor.run_step(params["server"], y, step=step,
                                            features=feats,
                                            collect_grads=False)
                dt = (time.time() - t0) / reps
            finally:
                tr.close()
            rows.append({
                "clients": K, "transport": name, "step_time_ms": dt * 1e3,
                "cut_bytes_per_client": res.report.cut_bytes_per_client,
            })
            _emit(f"transport/{name}_k{K}", dt * 1e6, "M=4 real execution")
    out["transport"] = rows


def bench_split_exec(out: dict) -> None:
    """Split-execution wall-clock per model family: every registered
    SplitProgram (dense/ssm/hybrid/moe/audio/vlm, reduced configs, 2
    clients) trains real steps through the Executor over InprocTransport.
    The per-family trajectory is the comparison baseline for future PRs —
    moe rows include the router aux loss riding the protocol's role-0 ->
    role-3 slot, and the sum/avg-merge exemplars (dense, moe) carry a
    secure-aggregation overhead column: the same steps with masked cut
    uplinks (source masking + masked merge) plus the one-time key-exchange
    bytes, vs the plain run.

    The same exemplars also carry accuracy-vs-bytes columns per compression
    scheme (repro.core.compression — topk 0.25 and int8): the ledger's
    compressed cut-uplink bytes, their ratio to the plain f32 uplink, the
    step time, and the loss deviation vs the uncompressed run (the accuracy
    cost the saved bytes buy)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.models import backbone, split_program
    from repro.runtime.executor import Executor
    from repro.transport import InprocTransport, TowerWorker

    batch, seq, reps = 2, 16, 3
    rows = []
    for arch in ("smollm-360m", "mamba2-1.3b", "zamba2-7b",
                 "deepseek-moe-16b", "whisper-tiny", "internvl2-26b"):
        cfg = get_arch(arch).reduced()
        program = split_program.get_program(cfg)
        params = backbone.init_params(cfg, jax.random.PRNGKey(0))
        towers_p, server_p = program.partition(params)
        loader = LMBatchLoader(cfg, batch, seq, seed=0)
        b = {k: jnp.asarray(v) for k, v in loader.next_batch().items()}
        feats, ctx = program.features(b), program.batch_ctx(b)

        def timed_run(secure: bool = False, compress=None):
            workers = [TowerWorker(k, program.tower_fwd(k), towers_p[k],
                                   compress=compress)
                       for k in range(program.num_clients)]
            with InprocTransport(workers) as tr:
                executor = Executor(tr, program.server_fwd, program.loss_fn,
                                    program.merge,
                                    microbatches=1, secure_agg=secure,
                                    compress=compress,
                                    **program.executor_kwargs)
                if secure:
                    executor.setup_secure()
                res = executor.run_step(server_p, ctx, features=feats,
                                        collect_grads=False)  # warm/compile
                t0 = time.time()
                for step in range(1, reps + 1):
                    res = executor.run_step(server_p, ctx, step=step,
                                            features=feats,
                                            collect_grads=False)
                return (time.time() - t0) / reps, res, executor

        dt, res, _ = timed_run(secure=False)
        row = {
            "family": cfg.family, "arch": cfg.name,
            "step_time_ms": dt * 1e3,
            "cut_bytes_per_client": res.report.cut_bytes_per_client,
        }
        if res.aux is not None:
            row["aux_loss"] = float(res.aux)
        # secure-agg overhead column for the sum/avg-merge exemplars
        if cfg.family in ("dense", "moe"):
            sec_dt, sec_res, sec_exec = timed_run(secure=True)
            row.update({
                "secure_step_time_ms": sec_dt * 1e3,
                "secure_overhead_x": sec_dt / dt,
                "secure_cut_bytes_per_client":
                    sec_res.report.cut_bytes_per_client,
                "key_exchange_bytes": sec_exec.keyx_ledger.total(),
            })
            _emit(f"split_exec/{cfg.family}_secure", sec_dt * 1e6,
                  f"{sec_dt / dt:.2f}x_vs_plain "
                  f"keyx={sec_exec.keyx_ledger.total()}B")
            # accuracy-vs-bytes per compression scheme: saved uplink bytes
            # against the loss deviation the lossy wire introduces
            plain_bytes = res.report.cut_bytes_per_client
            for scheme in ("topk", "int8"):
                c_dt, c_res, _ = timed_run(compress=scheme)
                c_bytes = c_res.report.cut_bytes_per_client
                loss_dev = abs(float(c_res.loss) - float(res.loss))
                row.update({
                    f"{scheme}_step_time_ms": c_dt * 1e3,
                    f"{scheme}_cut_bytes_per_client": c_bytes,
                    f"{scheme}_bytes_vs_plain": c_bytes / plain_bytes,
                    f"{scheme}_loss_dev_vs_plain": loss_dev,
                })
                _emit(f"split_exec/{cfg.family}_{scheme}", c_dt * 1e6,
                      f"{c_bytes / plain_bytes:.2f}x_bytes "
                      f"loss_dev={loss_dev:.4f}")
        rows.append(row)
        _emit(f"split_exec/{cfg.family}", dt * 1e6,
              f"{cfg.name} inproc K={program.num_clients}")
    out["split_exec"] = rows


def bench_split_pipeline(out: dict, *, full: bool = False) -> None:
    """Cross-step pipelined execution (repro.runtime.pipeline.StepPipeline):
    measured multi-step wall-clock at window W=1 (the per-step barrier) vs
    W=2 (step t+1 tower forwards overlapping step t's server backward +
    jacobian drain), plus the discrete-event prediction for the same
    schedule (``simulate_pipelined(steps, cross_step)``).

    Two sections:

    * per family — every registered SplitProgram over InprocTransport,
      real reduced-config numerics; the overlap here is whatever genuine
      thread parallelism the host gives tower forwards vs the role-0
      backward.
    * controlled — the paper-MLP program with KNOWN injected compute times
      (client forward sleep + role-0 loss sleep), per transport.  Because
      the compute times are known, the simulator's speedup prediction is
      directly comparable to the measured one — the rows carry both plus
      their ratio (the acceptance band is ~20%).
    """
    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_arch
    from repro.configs.vertical_mlp import MLPSplitConfig
    from repro.core import split_model, towers
    from repro.data.loader import LMBatchLoader
    from repro.models import backbone, split_program
    from repro.runtime import (LinkModel, StepPipeline, simulate_pipelined)
    from repro.runtime.engine import StepPlan
    from repro.runtime.executor import Executor
    from repro.transport import (InprocTransport, MultiprocTransport,
                                 TowerWorker, WorkerSpec, build_mlp_worker)

    rows = []

    def run_windowed(make_transport, make_executor, ctx_for, feats_for,
                     server_p, window, steps):
        """Drive ``steps`` training steps through StepPipeline(window) and
        return the per-step wall-clock (warm step excluded)."""
        tr = make_transport()
        try:
            executor = make_executor(tr)
            executor.run_step(server_p, ctx_for(0), features=feats_for(0),
                              collect_grads=False)  # warm / trace
            pipeline = StepPipeline(executor, window=window)
            t0 = time.time()
            for step in range(1, steps + 1):
                pipeline.submit(step, ctx_for(step),
                                features=feats_for(step))
                if pipeline.inflight >= window:
                    pipeline.collect(server_p, collect_grads=False)
            pipeline.flush(server_p, collect_grads=False)
            return (time.time() - t0) / steps
        finally:
            tr.close()

    # -- per family: real numerics over threads ------------------------------
    fam_steps = 3
    for arch in ("smollm-360m", "mamba2-1.3b", "zamba2-7b",
                 "deepseek-moe-16b", "whisper-tiny", "internvl2-26b"):
        cfg = get_arch(arch).reduced()
        program = split_program.get_program(cfg)
        params = backbone.init_params(cfg, jax.random.PRNGKey(0))
        towers_p, server_p = program.partition(params)
        b = {k: jnp.asarray(v) for k, v in
             LMBatchLoader(cfg, 2, 16, seed=0).next_batch().items()}
        feats, ctx = program.features(b), program.batch_ctx(b)

        per_w = {}
        for W in (1, 2):
            dt = run_windowed(
                lambda: InprocTransport(
                    [TowerWorker(k, program.tower_fwd(k), towers_p[k])
                     for k in range(program.num_clients)]),
                lambda tr: Executor(tr, program.server_fwd, program.loss_fn,
                                    program.merge,
                                    microbatches=1,
                                    **program.executor_kwargs),
                lambda step: ctx, lambda step: feats,
                server_p, W, fam_steps)
            per_w[W] = dt
            rows.append({
                "section": "family", "family": cfg.family, "arch": cfg.name,
                "transport": "inproc", "window": W,
                "step_time_ms": dt * 1e3,
                "speedup_vs_w1": per_w[1] / dt,
            })
            _emit(f"split_pipeline/{cfg.family}_w{W}", dt * 1e6,
                  f"{per_w[1] / dt:.2f}x_vs_w1")

    # -- controlled: known injected compute, per transport -------------------
    fwd_delay, server_delay, ctl_steps = 0.06, 0.06, 4
    K = 2
    cfg = MLPSplitConfig(
        name="pipeline_bench", input_dim=16 * K, num_classes=2,
        num_clients=K, client_feature_sizes=(16,) * K, tower_hidden=(32,),
        cut_dim=16, server_hidden=(32,), merge="avg",
    )
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, cfg.num_classes)

    def slow_loss(logits, labels):
        time.sleep(server_delay)
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    worker_kwargs = dict(cfg=cfg, param_seed=0, data_seed=0, batch=8,
                         microbatches=1, forward_delay_s=fwd_delay)
    transports = {
        "inproc": lambda: InprocTransport(
            [build_mlp_worker(k, **worker_kwargs) for k in range(K)]),
    }
    if full:
        transports["multiproc"] = lambda: MultiprocTransport(
            [WorkerSpec(build_mlp_worker, dict(worker_kwargs))
             for _ in range(K)])

    # the simulator clocks the SAME schedule with the injected times as the
    # compute model (rate 1.0 => flops are seconds); transfers are ~free on
    # loopback so the link is wide and flat
    plan = StepPlan(
        num_clients=K, microbatches=1,
        tower_fwd_flops=(fwd_delay,) * K, tower_bwd_flops=(0.003,) * K,
        server_flops=server_delay, cut_bytes=8 * cfg.cut_dim * 4,
        head_bytes=8 * cfg.num_classes * 4, merge="avg",
        cut_elements=8 * cfg.cut_dim,
    )
    link = LinkModel.uniform(K, latency_s=2e-4, bandwidth_bps=1e9,
                             client_flops_per_s=1.0, server_flops_per_s=1.0)
    sim = {W: simulate_pipelined(plan, link, steps=ctl_steps,
                                 cross_step=W).step_time_s
           for W in (1, 2)}
    predicted_speedup = sim[1] / sim[2]

    for name, make in transports.items():
        per_w = {}
        for W in (1, 2):
            dt = run_windowed(
                make,
                lambda tr: Executor(tr, towers.mlp_tower_apply, slow_loss,
                                    cfg.merge,
                                    microbatches=1),
                lambda step: y, lambda step: None,
                params["server"], W, ctl_steps)
            per_w[W] = dt
            rows.append({
                "section": "controlled", "transport": name, "window": W,
                "step_time_ms": dt * 1e3,
                "speedup_vs_w1": per_w[1] / dt,
                "sim_step_time_ms": sim[W] * 1e3,
                "sim_speedup_vs_w1": sim[1] / sim[W],
                "sim_over_measured": (sim[1] / sim[W]) / (per_w[1] / dt),
            })
            _emit(f"split_pipeline/controlled_{name}_w{W}", dt * 1e6,
                  f"measured {per_w[1] / dt:.2f}x "
                  f"sim {sim[1] / sim[W]:.2f}x")
    out["split_pipeline"] = rows
    print(f"split_pipeline: controlled W=2 predicted speedup "
          f"{predicted_speedup:.2f}x")


def bench_tree_sweep(out: dict) -> None:
    """Hierarchical-aggregation K-sweep: star vs fanout-2 tree
    (``runtime.topology.AggTree``) on the paper-MLP program, K in
    {4, 8, 16}, real execution over InprocTransport at window W=2 with
    M=2 microbatches.  Rows carry the measured per-step wall-clock, the
    audited role-0 per-step cut bytes (the O(K) -> O(F) reduction the tree
    exists for), and the pipelined clock's prediction of the same schedule
    on a link model with a FINITE role-0 NIC — the simulator half of the
    crossover claim."""
    import jax
    import jax.numpy as jnp

    from repro.configs.vertical_mlp import MLPSplitConfig
    from repro.core import split_model, towers
    from repro.runtime import (AggTree, LinkModel, StepPipeline, plan_step,
                               simulate_pipelined)
    from repro.runtime.executor import Executor
    from repro.transport import InprocTransport, TowerWorker

    # wide cut (4 MB/frame) so the role-0 merge is real memory-bandwidth
    # work: the star stacks K frames on the collector thread while the tree
    # sums them in the relay workers (jnp adds release the GIL, so relay
    # partial sums genuinely run in parallel)
    batch, M, W, steps = 256, 1, 2, 4
    rows = []
    for K in (4, 8, 16):
        cfg = MLPSplitConfig(
            name=f"tree_bench_k{K}", input_dim=16 * K, num_classes=2,
            num_clients=K, client_feature_sizes=(16,) * K,
            tower_hidden=(32,), cut_dim=4096, server_hidden=(64,),
            merge="avg",
        )
        params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        x = jax.random.normal(ks[0], (batch, cfg.input_dim))
        y = jax.random.randint(ks[1], (batch,), 0, cfg.num_classes)
        slices = split_model.feature_slices(cfg)
        feats = [x[:, jnp.asarray(s.indices)] for s in slices]

        def loss_fn(logits, labels):
            return split_model.softmax_xent(logits, labels, cfg.num_classes)

        def timed(tree):
            workers = [TowerWorker(k, towers.mlp_tower_apply,
                                   params["towers"][k]) for k in range(K)]
            tr = InprocTransport(workers)
            ex = None
            try:
                ex = Executor(tr, towers.mlp_tower_apply, loss_fn,
                              cfg.merge, microbatches=M,
                              agg_tree=tree)
                res = ex.run_step(params["server"], y, features=feats,
                                  collect_grads=False)  # warm / trace
                pipeline = StepPipeline(ex, window=W)
                t0 = time.time()
                for s in range(1, steps + 1):
                    pipeline.push(params["server"], y, step=s,
                                  features=feats, collect_grads=False)
                pipeline.flush(params["server"], collect_grads=False)
                dt = (time.time() - t0) / steps
            finally:
                # tree runs wrap the transport in a TreeRouter — close THAT
                (ex.transport if ex is not None else tr).close()
            ledger = res.ledger
            if tree is None:
                role0_rx = sum(ledger.bytes_with_tag(f"cut[{k}]")
                               for k in range(K))
            else:
                role0_rx = ledger.bytes_with_tag("tree_cut[0]")
            return dt, role0_rx

        star_dt, star_rx = timed(None)
        tree_dt, tree_rx = timed(AggTree(num_clients=K, fanout=2))

        link = LinkModel.uniform(K, server_bandwidth_bps=1e8)
        sim_star = simulate_pipelined(
            plan_step(cfg, batch_size=batch, microbatches=M), link,
            steps=steps, cross_step=W).step_time_s
        sim_tree = simulate_pipelined(
            plan_step(cfg, batch_size=batch, microbatches=M, tree_fanout=2),
            link, steps=steps, cross_step=W).step_time_s

        rows.append({
            "clients": K, "fanout": 2, "window": W, "microbatches": M,
            "star_step_time_ms": star_dt * 1e3,
            "tree_step_time_ms": tree_dt * 1e3,
            "measured_speedup": star_dt / tree_dt,
            "star_role0_cut_bytes_per_step": star_rx,
            "tree_role0_cut_bytes_per_step": tree_rx,
            "role0_bytes_ratio": star_rx / tree_rx,
            "sim_star_step_time_ms": sim_star * 1e3,
            "sim_tree_step_time_ms": sim_tree * 1e3,
            "sim_speedup": sim_star / sim_tree,
        })
        _emit(f"tree/star_k{K}", star_dt * 1e6, f"role0_rx={star_rx}B")
        _emit(f"tree/tree_k{K}", tree_dt * 1e6,
              f"{star_dt / tree_dt:.2f}x_vs_star "
              f"sim {sim_star / sim_tree:.2f}x "
              f"role0_rx={tree_rx}B")
    out["tree_sweep"] = rows
    crossover = next((r["clients"] for r in rows if r["sim_speedup"] > 1.0),
                     None)
    print(f"tree_sweep: finite-NIC clock predicts tree(F=2) wins from "
          f"K={crossover}")


def bench_split_serve(out: dict) -> None:
    """Split inference serving: continuous vs static batching on a
    mixed-length request workload (reduced dense arch, InprocTransport,
    K feature-holder threads).  Static batching drains the whole batch
    before admitting the next request, so a short request's retired slot
    idles while its batchmate finishes; continuous batching admits into
    the freed slot mid-flight.  Rows carry measured tokens/s and the
    Ledger-audited wire bytes per generated token — the perf claim the
    serving layer exists for."""
    import jax

    from repro.configs.base import get_arch
    from repro.models import backbone, split_program
    from repro.serve import SplitLMServer
    from repro.transport import InprocTransport, build_split_worker

    cfg = get_arch("smollm-360m").reduced()
    params = backbone.init_params(cfg, jax.random.PRNGKey(0))
    _, server = split_program.get_program(cfg).partition(params)
    K = cfg.vertical.num_clients

    # mixed lengths: short requests retire early, so continuous batching
    # has real slots to refill while static ones sit idle
    lens = [6, 12, 5, 10, 7, 9]
    new_toks = [14, 4, 12, 6, 10, 8]
    cache_len = max(s + n for s, n in zip(lens, new_toks))
    prompts = [jax.random.randint(jax.random.PRNGKey(i + 1), (s,), 0,
                                  cfg.vocab_size)
               for i, s in enumerate(lens)]

    rows = []
    per_mode = {}
    for continuous in (False, True):
        mode = "continuous" if continuous else "static"
        workers = [build_split_worker(k, cfg=cfg, seed=0, batch=2, seq=16)
                   for k in range(K)]
        with InprocTransport(workers) as tr:
            def run_once():
                srv = SplitLMServer(tr, cfg, server, cache_len=cache_len,
                                    max_batch=2, continuous=continuous)
                for p, n in zip(prompts, new_toks):
                    srv.submit(p, max_new_tokens=n)
                t0 = time.time()
                srv.run()
                return srv, time.time() - t0

            run_once()  # compile towers/slots; timing is the second pass
            srv, dt = run_once()
        wire = srv.wire_report()
        tokens = srv.stats["tokens"]
        per_mode[mode] = tokens / dt
        rows.append({
            "mode": mode, "clients": K, "max_batch": 2,
            "requests": len(prompts), "tokens": tokens,
            "decode_rounds": srv.stats["decode_rounds"],
            "tokens_per_s": tokens / dt,
            "wire_bytes_per_token": wire["bytes_per_token"],
            "decode_wire_bytes_per_token": wire["decode_bytes_per_token"],
        })
        _emit(f"split_serve/{mode}", dt * 1e6,
              f"{tokens / dt:.1f}tok/s "
              f"{wire['bytes_per_token']:.0f}B/tok")
    out["split_serve"] = rows
    print(f"split_serve: continuous {per_mode['continuous']:.1f} tok/s vs "
          f"static {per_mode['static']:.1f} tok/s "
          f"({per_mode['continuous'] / per_mode['static']:.2f}x)")


def run_paper_tables(steps: int, out: dict) -> None:
    from benchmarks import paper_tables as pt

    t0 = time.time()
    out["table2"] = pt.table2_centralized_vs_split(steps=steps)
    _emit("table2_centralized_vs_split", (time.time() - t0) * 1e6,
          f"steps={steps}")
    t0 = time.time()
    out["table3"] = pt.table3_merging_strategies(steps=steps)
    _emit("table3_merging_strategies", (time.time() - t0) * 1e6)
    t0 = time.time()
    out["table4"] = pt.table4_client_drops(steps=steps)
    _emit("table4_client_drops", (time.time() - t0) * 1e6)
    t0 = time.time()
    out["table5"] = pt.table5_communication()
    _emit("table5_communication", (time.time() - t0) * 1e6)
    t0 = time.time()
    out["table6"] = pt.table6_compute()
    _emit("table6_compute", (time.time() - t0) * 1e6)


SECTIONS = ("kernels", "runtime", "transport", "split_exec",
            "split_pipeline", "tree", "split_serve", "tables")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-budget tables (used for EXPERIMENTS.md)")
    ap.add_argument("--figures", action="store_true")
    ap.add_argument("--roofline", nargs="*", default=None,
                    help="dry-run json files to fold into the roofline table")
    ap.add_argument("--json", default=None)
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark sections to run "
                         f"(of {', '.join(SECTIONS)}); default: all")
    ap.add_argument("--bench-json", default="BENCH_split_exec.json",
                    help="machine-readable split-execution perf artifact "
                         "(per-family, per-transport, serial W=1 vs "
                         "cross-step W>1); tracked across PRs by CI")
    ap.add_argument("--check", action="store_true",
                    help="validate the --bench-json artifact against "
                         "benchmarks/bench_schema.json and exit (CI gate)")
    args = ap.parse_args(argv)

    if args.check:
        _check_bench_json(args.bench_json)
        return 0

    only = None
    if args.only:
        only = set(args.only.split(","))
        unknown = only - set(SECTIONS)
        if unknown:
            ap.error(f"unknown --only sections {sorted(unknown)}")

    def want(name: str) -> bool:
        return only is None or name in only

    print("name,us_per_call,derived")
    out: dict = {}
    if want("kernels"):
        bench_kernels()
    if want("runtime"):
        bench_runtime(out)
    if want("transport"):
        bench_transport(out)
    if want("split_exec"):
        bench_split_exec(out)
    if want("split_pipeline"):
        bench_split_pipeline(out, full=args.full)
    if want("tree"):
        bench_tree_sweep(out)
    if want("split_serve"):
        bench_split_serve(out)
    steps = 400 if args.full else 60
    if want("tables"):
        run_paper_tables(steps, out)
    if args.figures:
        from benchmarks import paper_tables as pt

        out["figure2"] = pt.figure2_training_curves(steps=steps)
    roofline_paths = args.roofline
    if roofline_paths is None:
        # default: fold in the dry-run matrices when present
        import os

        roofline_paths = [p for p in ("dryrun_single_v2.json",)
                          if os.path.exists(p)]
    if roofline_paths:
        from benchmarks.roofline import load_rows, to_markdown

        rows = load_rows(roofline_paths)
        out["roofline"] = rows
        print("\n== roofline (from the dry-run matrix) ==")
        print(to_markdown(rows))

    for name in ("runtime", "transport", "split_exec", "split_pipeline",
                 "tree_sweep", "split_serve", "table2", "table3", "table4",
                 "table5", "table6"):
        if name in out:
            print(f"\n== {name} ==")
            for row in out[name]:
                print(" ", {k: (round(v, 4) if isinstance(v, float) else v)
                            for k, v in row.items()})
    if args.bench_json and any(k in out for k in
                               ("split_exec", "split_pipeline",
                                "tree_sweep", "split_serve")):
        # the machine-readable perf artifact CI uploads: wall-clock per
        # family and per transport, serial (W=1) vs cross-step (W>1), the
        # star-vs-tree aggregation K-sweep, and serving throughput
        # (continuous vs static batching, wire bytes per token)
        artifact = {k: out[k] for k in ("split_exec", "split_pipeline",
                                        "tree_sweep", "split_serve")
                    if k in out}
        json.dump(artifact, open(args.bench_json, "w"), indent=1,
                  default=str)
        print(f"\nwrote {args.bench_json}")
    if args.json:
        json.dump(out, open(args.json, "w"), indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
