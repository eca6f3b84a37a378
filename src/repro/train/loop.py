"""Training loop: metrics, checkpointing, sharding-aware step dispatch.

Two drivers:

* :func:`train` — the monolithic jitted step (centralized or vertical; the
  protocol is arithmetic-identical, paper §3), one host, fastest clock.
* :func:`train_split` — SPLIT EXECUTION: any family (dense/ssm/hybrid/moe/
  audio/vlm — its :class:`~repro.models.split_program.SplitProgram`) trains
  through the protocol for real — per-role workers behind a
  :class:`~repro.transport.Transport` (threads or processes), the
  :class:`~repro.runtime.executor.Executor` driving ``step_schedule`` at
  role 0, tower params updating locally at the clients, and (``--runtime
  nowait``) EMA imputation filling deadline-missed seats in the real tower
  forward.  Step 0 is verified against the serial ``protocol_step``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.msgpack_ckpt import save_checkpoint
from repro.configs.base import ArchConfig
from repro.core import compat
from repro.core import compression as comp_lib
from repro.models import backbone
from repro.optim import AdamW
from repro.optim.schedules import linear_warmup_cosine


@dataclass
class TrainMetrics:
    steps: list[int] = field(default_factory=list)
    losses: list[float] = field(default_factory=list)
    step_times: list[float] = field(default_factory=list)
    # split runs: step-0 max |dgrad| vs the serial protocol_step, and the
    # tolerance it was held to (None when the check did not run)
    step0_max_dgrad: Optional[float] = None
    step0_atol: Optional[float] = None

    def log(self, step: int, loss: float, dt: float) -> None:
        self.steps.append(step)
        self.losses.append(loss)
        self.step_times.append(dt)

    def summary(self) -> dict:
        if not self.losses:
            return {}
        n = max(len(self.losses) // 10, 1)
        return {
            "first_loss": self.losses[0],
            "last_loss": self.losses[-1],
            "best_loss": min(self.losses),
            "mean_step_s": sum(self.step_times[1:]) / max(len(self.step_times) - 1, 1),
            "loss_drop": self.losses[0] - min(
                sum(self.losses[-n:]) / n, self.losses[-1]
            ),
        }


def train(
    cfg: ArchConfig,
    loader,
    *,
    steps: int = 100,
    learning_rate: float = 3e-4,
    warmup: int = 20,
    grad_clip: float = 1.0,
    log_every: int = 10,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 0,
    seed: int = 0,
    param_dtype=jnp.float32,
    print_fn: Callable = print,
) -> tuple[dict, TrainMetrics]:
    """Single-host training driver (the multi-pod path shares the step fn —
    see launch/dryrun.py for its sharded lowering)."""
    opt = AdamW(
        learning_rate=linear_warmup_cosine(learning_rate, warmup, steps),
        weight_decay=0.1,
        grad_clip_norm=grad_clip,
    )
    params = backbone.init_params(cfg, jax.random.PRNGKey(seed), param_dtype)
    opt_state = opt.init(params)
    step_fn = jax.jit(backbone.make_train_step(cfg, opt))

    metrics = TrainMetrics()
    it = iter(loader)
    for step in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        t0 = time.time()
        params, opt_state, loss = step_fn(params, opt_state, batch)
        loss = float(loss)
        dt = time.time() - t0
        metrics.log(step, loss, dt)
        if step % log_every == 0 or step == steps - 1:
            print_fn(f"step {step:5d}  loss {loss:8.4f}  {dt*1e3:8.1f} ms")
        if checkpoint_path and checkpoint_every and \
                (step + 1) % checkpoint_every == 0:
            save_checkpoint(checkpoint_path, params, step=step)
    if checkpoint_path:
        save_checkpoint(checkpoint_path, params, step=steps)
    return params, metrics


# ---------------------------------------------------------------------------
# split execution
# ---------------------------------------------------------------------------

def _make_transport(cfg: ArchConfig, transport: str, *, seed, batch, seq,
                    microbatches, learning_rate, warmup, steps, grad_clip,
                    straggler: Optional[int], straggler_delay_s: float):
    from repro.transport import (InprocTransport, MultiprocTransport,
                                 WorkerSpec, build_split_worker)

    K = cfg.vertical.num_clients
    kwargs = dict(cfg=cfg, seed=seed, batch=batch, seq=seq,
                  microbatches=microbatches, learning_rate=learning_rate,
                  warmup=warmup, steps=steps, grad_clip=grad_clip)

    def delay(k: int) -> float:
        return straggler_delay_s if k == straggler else 0.0

    if transport == "inproc":
        workers = [build_split_worker(k, forward_delay_s=delay(k), **kwargs)
                   for k in range(K)]
        return InprocTransport(workers)
    if transport == "multiproc":
        specs = [WorkerSpec(build_split_worker,
                            dict(kwargs, forward_delay_s=delay(k)))
                 for k in range(K)]
        return MultiprocTransport(specs)
    raise ValueError(f"unknown split transport {transport!r}")


def _verify_step0(res, program, tower_params, server_params, features, ctx,
                  microbatches, atol, print_fn, masked=False,
                  compressed=False, tree=False):
    """The acceptance identity: the transport's step-0 gradients must match
    the serial ``protocol_step`` on the same program decomposition.

    The reference is the mean of M per-microbatch serial steps — exactly
    what the Executor computes.  For batch-linear losses that equals the
    full-batch serial step; families with per-merge statistics (the moe
    router density/capacity behind the aux loss) are only equivalent at
    matching microbatch boundaries, so the reference must slice the same
    way the pipeline does.

    On a TPU the two sides differ only in the merge (the executor's Pallas
    kernel, the reference's jnp ``merge_stacked``); every other op is the
    same eager op on the same device.  For full-width smollm-360m on a v5e
    they agreed bit for bit, so the plain check keeps ``verify_atol``
    (1e-5) on the chip as on the CPU.

    ``masked`` labels the secure-aggregation run: the executor merged
    MASKED cuts, the reference is the unmasked serial step, and the match
    (to the loosened ``atol``) is the in-run proof that the pairwise masks
    cancelled — role 0 computed the true aggregate without ever observing
    a raw activation.

    ``compressed`` labels the compressed-wire run: ``program.
    protocol_step`` reads ``cfg.vertical.compression``, so the reference
    compresses its cuts/jacobians exactly like the transport path with the
    zero error-feedback residual every stream starts from — the match (to
    ``compression.STEP0_VERIFY_ATOL``) proves the lossy wire carried the
    step the codec defines, not silently degraded gradients.

    ``tree`` labels the aggregation-tree run: relays partial-summed their
    subtree's cuts before role 0 ever saw a frame, so the K-term merge was
    REASSOCIATED relative to the flat ``jnp.sum`` the serial reference
    computes.  f32 addition is not associative — the match is to
    ``runtime.topology.TREE_VERIFY_ATOL``, not bit-exact — but the relay
    accumulation order is deterministic (own cut, then children by id), so
    the residual is a fixed rounding difference, not nondeterminism."""
    M = microbatches
    B = jax.tree_util.tree_leaves(ctx)[0].shape[0]
    mbsz = B // M
    losses, tgs, sgs = [], [], []
    for m in range(M):
        sl = slice(m * mbsz, (m + 1) * mbsz)
        feats_m = [f[sl] for f in features]
        ctx_m = jax.tree_util.tree_map(lambda a: a[sl], ctx)
        loss_m, tg_m, sg_m, _ = program.protocol_step(
            tower_params, server_params, feats_m, ctx_m)
        losses.append(loss_m)
        tgs.append(tg_m)
        sgs.append(sg_m)
    loss_ref = sum(losses) / M
    tg_ref = jax.tree_util.tree_map(lambda *x: sum(x) / M, *tgs)
    sg_ref = jax.tree_util.tree_map(lambda *x: sum(x) / M, *sgs)
    got = jax.tree_util.tree_leaves((res.tower_grads, res.server_grads))
    want = jax.tree_util.tree_leaves((tg_ref, sg_ref))
    max_dev = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(got, want)
    )
    scale = max(float(jnp.max(jnp.abs(b))) for b in want)
    loss_dev = abs(float(res.loss) - float(loss_ref))
    what = "masked-merge " if masked else \
        "compressed-wire " if compressed else \
        "tree-merge " if tree else ""
    if max_dev > atol or loss_dev > atol:
        raise RuntimeError(
            f"step-0 {what}gradients diverge from the serial protocol_step: "
            f"max |dgrad| {max_dev:.3e}, |dloss| {loss_dev:.3e} > {atol:g} "
            f"(max |grad| {scale:.3e})")
    print_fn(f"step-0 {what}verification vs protocol_step: max |dgrad| "
             f"{max_dev:.2e} (<= {atol:g}) OK; max |grad| {scale:.2e}")
    return max_dev


def train_split(
    cfg: ArchConfig,
    loader,
    *,
    steps: int = 100,
    batch: int = 8,
    seq: int = 256,
    transport: str = "inproc",
    runtime: str = "serial",
    microbatches: int = 1,
    inflight_steps: int = 1,
    learning_rate: float = 3e-4,
    warmup: int = 20,
    grad_clip: float = 1.0,
    log_every: int = 10,
    seed: int = 0,
    straggler: Optional[int] = None,
    straggler_delay_s: float = 0.25,
    agg_tree_fanout: Optional[int] = None,
    verify_step0: bool = True,
    verify_atol: float = 1e-5,
    print_fn: Callable = print,
):
    """Train any vertically-split family through the Executor over a real
    transport.  Returns ({"towers": [...], "server": ...}, metrics, report).

    The decomposition comes from ``cfg``'s registered
    :class:`~repro.models.split_program.SplitProgram`: the driver is the
    role-0 server (server partition + the per-step batch context — labels,
    and for audio the decoder's teacher-forcing tokens); each feature
    holder owns its tower partition and regenerates its feature stream
    (tokens / mel-band frame slices / modality inputs) from the shared seed
    (see ``repro.transport.builders.build_split_worker``).  ``runtime``
    selects the schedule: ``serial`` (M=1 barrier), ``pipelined``
    (microbatched, staleness 0) or ``nowait`` (adaptive deadlines + EMA
    imputation in the real tower forward).  Families with a server-side
    auxiliary loss (moe) ship it role 0 -> role 3 through the protocol's
    ``aux_loss`` slot, audited in the ledger.

    ``inflight_steps`` is the cross-step window W driven through
    :class:`~repro.runtime.pipeline.StepPipeline`: at W > 1, step t+1's
    tower forwards are submitted (and computed, on threaded/process
    transports) while step t's server backward and jacobian drain are in
    flight.  Tower params then train on delayed gradients — one optimizer
    update behind the submitted forward (``report.staleness``); W = 1 is
    the exact ``run_step`` barrier.  Step 0 is verified against the serial
    ``protocol_step`` either way (its forwards always run on the initial
    params).

    Secure aggregation: ``cfg.vertical.secure_aggregation=True`` runs the
    one-time in-protocol key exchange over the transport, after which the
    workers mask every cut uplink at the source and role 0 merges masked
    cuts — it never observes a raw activation (``repro.core.secure_agg``).
    Step 0 then verifies the MASKED merge against the unmasked serial
    ``protocol_step`` to a tolerance loosened for the f32 mask-cancellation
    residue (valid at any W — round indices are per (step, microbatch)).
    Unsupported paths raise here rather than silently training unmasked:
    no-wait mode (a deadline-dropped client's masks cannot cancel) and
    ``merge_fn`` programs (the vlm sequence concat has no mask-cancelling
    sum).

    Cut compression: ``cfg.vertical.compression`` ("topk" | "int8") makes
    every worker compress its cut uplink at the source with error feedback
    and the executor compress the jacobian downlinks symmetrically
    (``repro.core.compression``); the step ledger then audits codec wire
    bytes (``compressed_cut[k]`` / ``compressed_jac[k]``).  Step 0 is
    verified against the serial ``protocol_step`` running the SAME
    compression (zero residual — the step-0 state of any stream, at any W)
    at the documented ``compression.STEP0_VERIFY_ATOL``.  Compression and
    secure aggregation are rejected together before any worker spawns:
    additive masks do not cancel through quantized/sparsified values.

    Hierarchical aggregation: ``agg_tree_fanout=F`` overlays a fanout-F
    :class:`~repro.runtime.topology.AggTree` on the transport — relay
    workers partial-sum their subtree's cut uplinks and role 0
    merges/fans-out only ``min(F, K)`` frames per microbatch instead of K
    (composes with secure aggregation: masked partial sums still cancel at
    the root).  Requires an additive merge ("sum"/"avg"); rejected loudly
    with compression, ``merge_fn`` programs, and no-wait mode before any
    worker spawns.  Step 0 verifies to ``runtime.topology.
    TREE_VERIFY_ATOL`` — the tree REASSOCIATES the f32 sum, so the match
    is a documented rounding tolerance, not bit-exact.
    """
    from repro.models.split_program import get_program
    from repro.runtime.engine import MODES
    from repro.runtime.executor import Executor
    from repro.runtime.pipeline import StepPipeline

    if cfg.vertical is None:
        raise ValueError("train_split needs a vertical config")
    if runtime not in MODES:
        raise ValueError(f"runtime must be one of {MODES}, got {runtime!r}")
    if inflight_steps < 1:
        raise ValueError(f"inflight_steps must be >= 1, got {inflight_steps}")
    # the executor has two modes: no-wait, and the barrier that both the
    # serial and the (microbatched) pipelined runtime run
    mode = "nowait" if runtime == "nowait" else "serial"
    M = 1 if runtime == "serial" else microbatches
    W = inflight_steps

    program = get_program(cfg)
    secure = cfg.vertical.secure_aggregation
    compress = cfg.vertical.compression
    # fail actionably BEFORE spawning workers: every unsound composition
    # (a silently unmasked secure run would be a privacy hole; a codec
    # frame cannot be partial-summed; ...) rejects through the ONE compat
    # matrix instead of surfacing as a mid-run Executor/worker error
    compat.check(
        "train", secure=secure, compress=compress, tree=agg_tree_fanout,
        nowait=runtime == "nowait", merge_fn=program.merge_fn,
        merge=program.merge, context=f"train_split({cfg.name})")
    agg_tree = None
    if agg_tree_fanout is not None:
        from repro.runtime.topology import AggTree
        agg_tree = AggTree(num_clients=cfg.vertical.num_clients,
                           fanout=agg_tree_fanout)
    # no name holds the whole tree: once the server updates, its initial
    # params must be free to go
    tower_params, server_params = program.partition(
        backbone.init_params(cfg, jax.random.PRNGKey(seed)))

    opt = AdamW(
        learning_rate=linear_warmup_cosine(learning_rate, warmup, steps),
        weight_decay=0.1, grad_clip_norm=grad_clip,
    )
    # made at the first update, after the step-0 check: the check's second
    # server step then runs without the AdamW moments (twice the server
    # params) on the device
    opt_state = None

    tr = _make_transport(
        cfg, transport, seed=seed, batch=batch, seq=seq, microbatches=M,
        learning_rate=learning_rate, warmup=warmup, steps=steps,
        grad_clip=grad_clip, straggler=straggler,
        straggler_delay_s=straggler_delay_s,
    )
    print_fn(f"split execution ({transport}): role 0 on "
             f"{jax.default_backend()}, {tr.num_clients} tower workers on "
             f"{tr.tower_platform}")
    metrics = TrainMetrics()
    report = None
    max_staleness = 0
    ema_state = None
    b0 = None  # step-0 batch retained for the deferred verification
    it = iter(loader)
    t_last = time.time()

    def handle(res):
        """Consume one collected step: verify (step 0), update the server,
        thread the EMA state, log."""
        nonlocal server_params, opt_state, ema_state, report, t_last, \
            max_staleness
        max_staleness = max(max_staleness,
                            getattr(res.report, "staleness", 0))
        if res.step == 0 and verify_step0:
            if mode == "nowait" and res.report.total_misses > 0:
                # the §3 identity only holds at staleness 0: a step-0
                # deadline miss legitimately reroutes gradients through
                # the EMA imputation
                print_fn("step-0 verification skipped: "
                         f"{res.report.total_misses} no-wait deadline "
                         "miss(es) — gradients are intentionally "
                         "imputed, not serial")
            else:
                ctx0 = program.batch_ctx(b0)
                # masked merges carry the f32 mask-cancellation residue
                # (secure_agg.cancellation_bound): loosen the tolerance.
                # compressed wires verify against a reference running the
                # same codec, at the documented compression tolerance
                if secure:
                    atol = max(verify_atol, 1e-3)
                elif compress is not None:
                    atol = max(verify_atol, comp_lib.STEP0_VERIFY_ATOL)
                elif agg_tree is not None:
                    # relay partial sums reassociate the f32 K-term merge
                    from repro.runtime.topology import TREE_VERIFY_ATOL
                    atol = max(verify_atol, TREE_VERIFY_ATOL)
                else:
                    atol = verify_atol
                metrics.step0_max_dgrad = _verify_step0(
                    res, program, tower_params, server_params,
                    program.features(b0), ctx0, M, atol, print_fn,
                    masked=secure, compressed=compress is not None,
                    tree=agg_tree is not None)
                metrics.step0_atol = atol
                if compress is not None:
                    comp_bytes = res.ledger.bytes_with_tag(
                        executor._schedule.cuts[0].tag)
                    cut0 = program.tower_fwds[0](
                        tower_params[0], program.features(b0)[0][:batch // M])
                    raw_bytes = M * comp_lib.payload_bytes(cut0, None)
                    print_fn(
                        f"compressed cut uplink ({compress}): {comp_bytes} B"
                        f"/client/step vs {raw_bytes} B raw "
                        f"({comp_bytes / raw_bytes:.2f}x)")
            if program.has_aux:
                aux_bytes = res.ledger.bytes_with_tag("aux_loss")
                print_fn(f"router aux loss {float(res.aux):.6f} "
                         "transported role0 -> role3 through the "
                         f"protocol aux slot ({aux_bytes} B in ledger)")
        if opt_state is None:
            opt_state = opt.init(server_params)
        with jax.profiler.TraceAnnotation("train.server_update",
                                          step=res.step):
            server_params, opt_state = opt.update(
                server_params, res.server_grads, opt_state)
        ema_state = res.ema_state
        report = res.report
        loss = float(res.loss)
        now = time.time()
        dt, t_last = now - t_last, now
        metrics.log(res.step, loss, dt)
        if res.step % log_every == 0 or res.step == steps - 1:
            miss = res.report.total_misses if res.report else 0
            print_fn(f"step {res.step:5d}  loss {loss:8.4f}  "
                     f"{dt*1e3:8.1f} ms"
                     f"  [{transport}/{runtime}"
                     + (f" W={W}" if W > 1 else "")
                     + (f" aux={float(res.aux):.4f}"
                        if res.aux is not None else "")
                     + (f" misses={miss}" if mode == "nowait" else "")
                     + "]")

    try:
        # inside the try: Executor.__init__ validates program/runtime
        # compatibility (e.g. a merge_fn program cannot EMA-impute) and the
        # spawned workers must not leak when it raises
        executor = Executor(tr, program.server_fwd, program.loss_fn,
                            program.merge, mode=mode, microbatches=M,
                            secure_agg=secure, compress=compress,
                            topk_fraction=cfg.vertical.topk_fraction,
                            agg_tree=agg_tree,
                            **program.executor_kwargs)
        # the Executor wraps a tree run's transport in a TreeRouter; rebind
        # so the finally below closes the router (which stops its routing
        # pump before tearing down the base transport)
        tr = executor.transport
        if agg_tree is not None:
            print_fn(f"aggregation tree: fanout {agg_tree.fanout}, depth "
                     f"{agg_tree.depth}, {len(agg_tree.relays)} relay(s) — "
                     f"role 0 merges {len(agg_tree.top_level)} frames/mb "
                     f"instead of {cfg.vertical.num_clients}")
        if secure:
            kx = executor.setup_secure()
            print_fn(f"secure aggregation: pairwise key exchange complete "
                     f"({kx.total()} B over {transport}; cut uplinks are "
                     "masked at the source, role 0 observes no raw "
                     "activation)")
        pipeline = StepPipeline(executor, window=W)

        def collect_one():
            target = pipeline.next_collect
            handle(pipeline.collect(
                server_params, ema_state=ema_state,
                collect_grads=(target == 0 and verify_step0)))

        for step in range(steps):
            b = next(it)
            if step == 0:
                b0 = b
            pipeline.submit(step, program.batch_ctx(b))
            if pipeline.inflight >= W:
                collect_one()
        while pipeline.inflight:  # drain the fill (steps < W included)
            collect_one()
        final_towers = _collect_tower_params(tr)
    finally:
        tr.close()
    if report is not None and hasattr(report, "staleness"):
        # the drain-collected tail always has staleness 0; surface the
        # run's actual delayed-gradient lag on the returned report
        report.staleness = max_staleness
    return ({"towers": final_towers, "server": server_params},
            metrics, report)


def _collect_tower_params(tr):
    """Fetch each client's final tower params (checkpointing/inspection)."""
    K = tr.num_clients
    out: list = [None] * K
    for k in range(K):
        tr.submit(k, {"op": "get_params"})
    seen = 0
    while seen < K:
        got = tr.next_response(60.0)
        if got is None:
            raise RuntimeError("timed out collecting tower params")
        k, resp = got
        if resp["op"] == "params":
            out[k] = jax.tree_util.tree_map(jnp.asarray, resp["params"])
            seen += 1
    return out
