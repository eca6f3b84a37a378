"""Cross-step pipelined execution (Executor.submit_step/collect_step +
runtime.pipeline.StepPipeline): W=1 must reproduce the run_step barrier
bit-for-bit for every family, W=2 must implement the documented
delayed-gradient semantics exactly, the per-step ledgers must stay exact,
and the discrete-event clock must predict the measured overlap."""
import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vertical_mlp import MLPSplitConfig
from repro.core import protocol, split_model, towers
from repro.runtime import LinkModel, StepPipeline, simulate_pipelined
from repro.runtime.engine import StepPlan
from repro.runtime.executor import Executor
from repro.transport import InprocTransport, SimTransport, TowerWorker
from repro.transport.builders import _sgd

TINY = MLPSplitConfig(
    name="pipeline_tiny", input_dim=16, num_classes=2, num_clients=2,
    client_feature_sizes=(8, 8), tower_hidden=(16,), cut_dim=8,
    server_hidden=(16,), merge="avg",
)

FAMILY_ARCHS = [
    ("dense", "smollm-360m"),
    ("ssm", "mamba2-1.3b"),
    ("hybrid", "zamba2-7b"),
    ("moe", "deepseek-moe-16b"),
    ("audio", "whisper-tiny"),
    ("vlm", "internvl2-26b"),
]


def _hold_role0(seconds):
    """Injected role-0 compute inside a loss: the server step is compiled,
    so a plain sleep would run once, when it is traced; an ordered host
    callback sleeps on every execution, one microbatch after another."""
    import time as _time

    jax.debug.callback(lambda: _time.sleep(seconds), ordered=True)


def _mlp_steps(cfg, n_steps, batch=8, seed=0):
    """Per-step features/labels streams for the tiny MLP."""
    slices = split_model.feature_slices(cfg)
    idx = [jnp.asarray(s.indices) for s in slices]
    feats, ys = [], []
    for s in range(n_steps):
        ks = jax.random.split(jax.random.PRNGKey(seed + 100 + s), 2)
        x = jax.random.normal(ks[0], (batch, cfg.input_dim))
        feats.append([x[:, i] for i in idx])
        ys.append(jax.random.randint(ks[1], (batch,), 0, cfg.num_classes))
    return feats, ys


# ---------------------------------------------------------------------------
# W=1: StepPipeline == run_step barrier, bit-for-bit, every family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,arch", FAMILY_ARCHS)
def test_pipeline_w1_bitexact_vs_run_step(family, arch):
    """The regression pin: StepPipeline(window=1) must execute the exact
    transport-call sequence of run_step — identical losses, step-0
    gradients, and ledger bytes over a 2-step run with local tower updates
    and server updates, for all six families."""
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.models import backbone, split_program

    cfg = get_arch(arch).reduced()
    assert cfg.family == family
    program = split_program.get_program(cfg)
    params = backbone.init_params(cfg, jax.random.PRNGKey(0))
    towers_p, server_p0 = program.partition(params)
    loader = iter(LMBatchLoader(cfg, 2, 16, seed=0))
    batches = [
        {k: jnp.asarray(v) for k, v in next(loader).items()}
        for _ in range(2)
    ]
    lr = 0.1

    def run(pipelined: bool):
        workers = [TowerWorker(k, program.tower_fwd(k), towers_p[k],
                               optimizer=_sgd(lr))
                   for k in range(program.num_clients)]
        tr = SimTransport(workers)
        server_p = server_p0
        out = []
        try:
            executor = Executor(tr, program.server_fwd, program.loss_fn,
                                program.merge,
                                microbatches=1, **program.executor_kwargs)
            pipeline = StepPipeline(executor, window=1)
            for step, b in enumerate(batches):
                ctx = program.batch_ctx(b)
                feats = program.features(b)
                if pipelined:
                    res = pipeline.push(server_p, ctx, step=step,
                                        features=feats,
                                        collect_grads=(step == 0))
                else:
                    res = executor.run_step(server_p, ctx, step=step,
                                            features=feats,
                                            collect_grads=(step == 0))
                server_p = jax.tree_util.tree_map(
                    lambda p, g: p - lr * g, server_p, res.server_grads)
                out.append(res)
        finally:
            tr.close()
        return out

    a, b = run(True), run(False)
    for ra, rb in zip(a, b):
        assert float(ra.loss) == float(rb.loss)
        assert ra.ledger.total() == rb.ledger.total()
        assert ra.report.staleness == 0
    for la, lb in zip(jax.tree_util.tree_leaves((a[0].tower_grads,
                                                 a[0].server_grads)),
                      jax.tree_util.tree_leaves((b[0].tower_grads,
                                                 b[0].server_grads))):
        np.testing.assert_array_equal(la, lb)


# ---------------------------------------------------------------------------
# W=2: delayed-gradient semantics, verified against an explicit reference
# ---------------------------------------------------------------------------

def test_pipeline_w2_matches_delayed_gradient_reference():
    """At window 2, tower params lag the submitted forward by one optimizer
    update (the worker's FIFO processes step t+1 forwards before step t's
    finish), and backwards linearize at the forward's param snapshot.  The
    whole run must match a hand-rolled reference implementing exactly those
    semantics with serial protocol_steps."""
    cfg = TINY
    S, W, lr = 4, 2, 0.2
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    feats_by_step, y_by_step = _mlp_steps(cfg, S)

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    # -- reference: explicit delayed-gradient schedule ----------------------
    tau = list(params["towers"])  # worker-held params
    sigma = params["server"]
    snap = {}
    pending = deque()
    ref_losses = []

    def ref_collect(t):
        nonlocal tau, sigma
        loss_t, tg_t, sg_t, _ = protocol.protocol_step(
            towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
            snap[t], sigma, feats_by_step[t], y_by_step[t], cfg.merge)
        sigma = jax.tree_util.tree_map(lambda p, g: p - lr * g, sigma, sg_t)
        # the worker applies the snapshot-linearized grads to its CURRENT
        # params (which may already include a later... earlier step's update)
        tau = [jax.tree_util.tree_map(lambda p, g: p - lr * g, tp, g)
               for tp, g in zip(tau, tg_t)]
        ref_losses.append(float(loss_t))

    for s in range(S):
        snap[s] = list(tau)  # params the step-s forwards run under
        pending.append(s)
        if len(pending) == W:
            ref_collect(pending.popleft())
    while pending:
        ref_collect(pending.popleft())

    # -- real pipeline over SimTransport ------------------------------------
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                           optimizer=_sgd(lr))
               for k in range(cfg.num_clients)]
    tr = SimTransport(workers)
    sigma_real = params["server"]
    got_losses, staleness = [], []
    ledger_totals = []
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=1)
        pipeline = StepPipeline(executor, window=W)

        def consume(res):
            nonlocal sigma_real
            sigma_real = jax.tree_util.tree_map(
                lambda p, g: p - lr * g, sigma_real, res.server_grads)
            got_losses.append(float(res.loss))
            staleness.append(res.report.staleness)
            ledger_totals.append(res.ledger.total())

        for s in range(S):
            res = pipeline.push(sigma_real, y_by_step[s], step=s,
                                features=feats_by_step[s],
                                collect_grads=False)
            if res is not None:
                consume(res)
        for res in pipeline.flush(sigma_real, collect_grads=False):
            consume(res)
    finally:
        tr.close()

    np.testing.assert_allclose(got_losses, ref_losses, atol=1e-6, rtol=1e-6)
    # steady-state staleness is W-1; the flush-collected tail step is 0
    assert staleness == [1, 1, 1, 0]
    # per-step ledgers: every step audits the full schedule's bytes
    assert len(set(ledger_totals)) == 1
    # W=2 genuinely diverges from the serial (W=1) trajectory after step 1
    # (step 1's forwards ran on pre-update params) — guard against the
    # pipeline silently degenerating into a barrier
    serial_losses = []
    tau_s, sigma_s = list(params["towers"]), params["server"]
    for s in range(S):
        loss_t, tg_t, sg_t, _ = protocol.protocol_step(
            towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
            tau_s, sigma_s, feats_by_step[s], y_by_step[s], cfg.merge)
        sigma_s = jax.tree_util.tree_map(lambda p, g: p - lr * g, sigma_s,
                                         sg_t)
        tau_s = [jax.tree_util.tree_map(lambda p, g: p - lr * g, tp, g)
                 for tp, g in zip(tau_s, tg_t)]
        serial_losses.append(float(loss_t))
    assert got_losses[0] == pytest.approx(serial_losses[0], abs=1e-6)
    assert any(abs(a - b) > 1e-7
               for a, b in zip(got_losses[1:], serial_losses[1:]))


def test_pipeline_window_validation():
    workers = [TowerWorker(k, towers.mlp_tower_apply, None)
               for k in range(2)]
    tr = SimTransport(workers)
    executor = Executor(tr, lambda *a: None, lambda *a: None, "avg")
    with pytest.raises(ValueError):
        StepPipeline(executor, window=0)
    p = StepPipeline(executor, window=2)
    with pytest.raises(RuntimeError):
        p.collect(None)
    tr.close()


# ---------------------------------------------------------------------------
# wall-clock: W=2 overlaps step t+1 forwards with step t server compute
# ---------------------------------------------------------------------------

def test_pipeline_w2_beats_w1_wallclock_and_sim_predicts_it():
    """With known injected compute (client forward sleep + role-0 loss
    sleep), the W=2 window must beat the W=1 barrier on a threaded
    transport, and ``simulate_pipelined(steps, cross_step)`` must predict
    the measured speedup (generous band here; benchmarks carry the tight
    number)."""
    import time as _time

    cfg = TINY
    fwd_delay, server_delay, S = 0.2, 0.2, 3
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    feats_by_step, y_by_step = _mlp_steps(cfg, S + 1)

    def slow_loss(logits, labels):
        _hold_role0(server_delay)
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    def run(window):
        workers = [TowerWorker(k, towers.mlp_tower_apply,
                               params["towers"][k],
                               forward_delay_s=fwd_delay)
                   for k in range(cfg.num_clients)]
        with InprocTransport(workers) as tr:
            executor = Executor(tr, towers.mlp_tower_apply, slow_loss,
                                cfg.merge, microbatches=1)
            # warm step: jax dispatch/trace outside the timed region
            executor.run_step(params["server"], y_by_step[S],
                              features=feats_by_step[S],
                              collect_grads=False)
            pipeline = StepPipeline(executor, window=window)
            t0 = _time.time()
            for s in range(S):
                pipeline.push(params["server"], y_by_step[s], step=s + 1,
                              features=feats_by_step[s],
                              collect_grads=False)
            pipeline.flush(params["server"], collect_grads=False)
            return (_time.time() - t0) / S

    t1, t2 = run(1), run(2)
    measured = t1 / t2
    assert measured > 1.1, (t1, t2)

    plan = StepPlan(
        num_clients=cfg.num_clients, microbatches=1,
        tower_fwd_flops=(fwd_delay,) * cfg.num_clients,
        tower_bwd_flops=(0.003,) * cfg.num_clients,
        server_flops=server_delay, cut_bytes=8 * cfg.cut_dim * 4,
        head_bytes=8 * cfg.num_classes * 4, merge=cfg.merge,
        cut_elements=8 * cfg.cut_dim,
    )
    link = LinkModel.uniform(cfg.num_clients, latency_s=2e-4,
                             bandwidth_bps=1e9, client_flops_per_s=1.0,
                             server_flops_per_s=1.0)
    sim = {w: simulate_pipelined(plan, link, steps=S,
                                 cross_step=w).step_time_s for w in (1, 2)}
    predicted = sim[1] / sim[2]
    assert predicted > 1.1
    # the clock and the wall agree on the size of the win
    assert 0.6 < predicted / measured < 1.4, (predicted, measured)


def test_pipeline_m2_w2_wallclock_band_matches_sim():
    """Regression for the M>1 ∧ W>1 client-FIFO order: the driver ships all
    M of a step's forwards at submit time, so at W=2 a client's queue holds
    step t+1's TWO forwards before step t's backwards arrive.  The clock
    acquires every forward slot at step-release time to model exactly that
    order — pin its prediction band against a measured inproc run with
    injected compute."""
    import time as _time

    cfg = TINY
    fwd_delay, server_delay, S, M = 0.1, 0.1, 3, 2
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    feats_by_step, y_by_step = _mlp_steps(cfg, S + 1)

    def slow_loss(logits, labels):
        _hold_role0(server_delay)  # per microbatch: role-0 merge+head work
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    def run(window):
        workers = [TowerWorker(k, towers.mlp_tower_apply,
                               params["towers"][k],
                               forward_delay_s=fwd_delay)
                   for k in range(cfg.num_clients)]
        with InprocTransport(workers) as tr:
            executor = Executor(tr, towers.mlp_tower_apply, slow_loss,
                                cfg.merge, microbatches=M)
            executor.run_step(params["server"], y_by_step[S],
                              features=feats_by_step[S],
                              collect_grads=False)
            pipeline = StepPipeline(executor, window=window)
            t0 = _time.time()
            for s in range(S):
                pipeline.push(params["server"], y_by_step[s], step=s + 1,
                              features=feats_by_step[s],
                              collect_grads=False)
            pipeline.flush(params["server"], collect_grads=False)
            return (_time.time() - t0) / S

    t1, t2 = run(1), run(2)
    measured = t1 / t2

    plan = StepPlan(
        num_clients=cfg.num_clients, microbatches=M,
        tower_fwd_flops=(fwd_delay,) * cfg.num_clients,
        tower_bwd_flops=(0.003,) * cfg.num_clients,
        server_flops=server_delay, cut_bytes=4 * cfg.cut_dim * 4,
        head_bytes=4 * cfg.num_classes * 4, merge=cfg.merge,
        cut_elements=4 * cfg.cut_dim,
    )
    link = LinkModel.uniform(cfg.num_clients, latency_s=2e-4,
                             bandwidth_bps=1e9, client_flops_per_s=1.0,
                             server_flops_per_s=1.0)
    sim = {w: simulate_pipelined(plan, link, steps=S,
                                 cross_step=w).step_time_s for w in (1, 2)}
    predicted = sim[1] / sim[2]
    assert sim[2] < sim[1]
    # the clock and the wall agree on the size of the win with microbatch
    # queues in play (the pre-fix clock chained forwards per-mb and
    # overpredicted the W=2 win here)
    assert 0.6 < predicted / measured < 1.4, (predicted, measured)


# ---------------------------------------------------------------------------
# engine: the cross-step clock itself
# ---------------------------------------------------------------------------

def test_simulate_pipelined_cross_step_window():
    plan = StepPlan(num_clients=2, microbatches=1,
                    tower_fwd_flops=(1.0, 1.0), tower_bwd_flops=(0.1, 0.1),
                    server_flops=1.0, cut_bytes=8, head_bytes=8, merge="avg",
                    cut_elements=2, bytes_per_elt=4)
    link = LinkModel.uniform(2, latency_s=1e-4, bandwidth_bps=1e12,
                             client_flops_per_s=1.0, server_flops_per_s=1.0)
    single = simulate_pipelined(plan, link)
    w1 = simulate_pipelined(plan, link, steps=6, cross_step=1)
    w2 = simulate_pipelined(plan, link, steps=6, cross_step=2)
    # W=1 multi-step is the barrier: amortized step time ~= the single step
    # (plus only the step_done ack latency)
    assert single.step_time_s <= w1.step_time_s <= single.step_time_s * 1.05
    # W=2 overlaps the next step's forwards with the server backward
    assert w2.step_time_s < 0.8 * w1.step_time_s
    assert w2.total_time_s == pytest.approx(w2.step_time_s * 6)
    assert w2.cross_step == 2 and w2.steps == 6
    assert len(w2.live) == 6 * plan.microbatches
    # the window is a cap, not a requirement: W > steps clamps
    wbig = simulate_pipelined(plan, link, steps=2, cross_step=8)
    assert wbig.total_time_s > 0

    with pytest.raises(ValueError):
        simulate_pipelined(plan, link, steps=0)
    with pytest.raises(ValueError):
        simulate_pipelined(plan, link, cross_step=0)


def test_simulate_cross_step_nowait_straggler_bounded():
    """No-wait composes with the cross-step window: a straggler misses its
    merges without stalling the multi-step run."""
    plan = StepPlan(num_clients=3, microbatches=2,
                    tower_fwd_flops=(1.0,) * 3, tower_bwd_flops=(0.1,) * 3,
                    server_flops=0.6, cut_bytes=8, head_bytes=8, merge="avg",
                    cut_elements=2, bytes_per_elt=4)
    link = LinkModel.uniform(3, latency_s=1e-4, bandwidth_bps=1e12,
                             client_flops_per_s=1.0, server_flops_per_s=1.0
                             ).with_straggler(2, slowdown=10.0)
    wait = simulate_pipelined(plan, link, mode="pipelined", steps=3,
                              cross_step=2)
    nowait = simulate_pipelined(plan, link, mode="nowait", steps=3,
                                cross_step=2)
    assert nowait.misses_per_client[2] > 0
    assert sum(nowait.misses_per_client) == nowait.misses_per_client[2]
    assert nowait.step_time_s < wait.step_time_s


# ---------------------------------------------------------------------------
# runtime-aware placement over plan_from_arch (ROADMAP open item)
# ---------------------------------------------------------------------------

def test_advise_arch_split_depth_sweeps_tower_layers():
    from repro.configs.base import get_arch
    from repro.core.costs import advise_arch_split_depth

    cfg = dataclasses.replace(get_arch("smollm-360m").reduced(),
                              num_layers=6)
    kw = dict(batch_size=8, seq_len=32, microbatches=4)
    serial = advise_arch_split_depth(cfg, objective="serial", **kw)
    pipe = advise_arch_split_depth(cfg, objective="pipelined", **kw)
    pipe_w2 = advise_arch_split_depth(cfg, objective="pipelined",
                                      cross_step=2, **kw)

    for r in (serial, pipe, pipe_w2):
        # every placement of the 6-layer stack is clocked (server keeps >=1)
        assert set(r["step_time_s_by_depth"]) == {1, 2, 3, 4, 5}
        d = r["recommended_tower_layers"]
        assert r["step_time_s_by_depth"][d] == min(
            r["step_time_s_by_depth"].values())
    # the serial clock pays every tower K-sequentially while the pipelined
    # clock runs towers in parallel against the serialized server — under
    # the default (fast-server) rates they disagree on the placement
    assert (serial["recommended_tower_layers"]
            != pipe["recommended_tower_layers"])
    # the cross-step window helps every placement where overlap exists, but
    # it is NOT free at placements it cannot improve: the driver ships step
    # t+1's M forwards before step t's backwards, so on client-bound
    # placements the backwards queue behind a full step of forwards and the
    # short run's drain stretches.  The clock models that FIFO order
    # exactly (at M=4 the microbatch pipeline already supplies most of the
    # overlap) — bound the worst-case stretch instead of forbidding it, and
    # require the best placement to stay competitive.
    for d in pipe["step_time_s_by_depth"]:
        assert (pipe_w2["step_time_s_by_depth"][d]
                <= pipe["step_time_s_by_depth"][d] * 1.15)
    assert (min(pipe_w2["step_time_s_by_depth"].values())
            <= min(pipe["step_time_s_by_depth"].values()) * 1.05)

    with pytest.raises(ValueError):
        advise_arch_split_depth(cfg, objective="heuristic", **kw)
    with pytest.raises(ValueError):
        advise_arch_split_depth(cfg.with_vertical(None), **kw)
