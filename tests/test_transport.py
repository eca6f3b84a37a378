"""Transport equivalence: the same Executor numerics over threads and real
loopback sockets must reproduce the serial ``protocol_step`` gradients at
staleness 0, and the per-role Ledger byte counts must match the analytic
``core.costs`` model when the payloads cross an actual process boundary."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vertical_mlp import BANK_MARKETING, MLPSplitConfig
from repro.core import costs, protocol, split_model, towers
from repro.runtime.deadline import AdaptiveDeadline
from repro.runtime.executor import Executor
from repro.transport import (InprocTransport, MultiprocTransport, SimTransport,
                             TowerWorker, WorkerSpec, build_mlp_worker)

TINY = MLPSplitConfig(
    name="transport_tiny", input_dim=16, num_classes=2, num_clients=2,
    client_feature_sizes=(8, 8), tower_hidden=(16,), cut_dim=8,
    server_hidden=(16,), merge="avg",
)

TINY3 = MLPSplitConfig(
    name="transport_tiny3", input_dim=12, num_classes=2, num_clients=3,
    client_feature_sizes=(4, 4, 4), tower_hidden=(16,), cut_dim=8,
    server_hidden=(16,), merge="avg",
)


def _setup(cfg, seed=0, batch=16):
    key = jax.random.PRNGKey(seed)
    params = split_model.init_split_mlp(key, cfg)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (batch, cfg.input_dim))
    y = jax.random.randint(ks[1], (batch,), 0, cfg.num_classes)
    slices = split_model.feature_slices(cfg)
    feats = [x[:, jnp.asarray(s.indices)] for s in slices]

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    return params, feats, y, loss_fn


def _assert_trees_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=1e-4)


def _precompile(workers, feats, rows):
    """Compile each worker's forward and backward at the ``rows``-row
    microbatch shape before a wall-clock test, so that the injected sleeps
    and not the first call's compile set when the cuts arrive."""
    for w, f in zip(workers, feats):
        delay, w.forward_delay_s = w.forward_delay_s, 0.0
        cut = w.handle({"op": "forward", "step": -1, "mb": 0,
                        "feats": f[:rows]})["cut"]
        w.handle({"op": "backward", "step": -1, "mb": 0,
                  "jac": jnp.zeros_like(cut)})
        w.handle({"op": "finish_step", "step": -1, "microbatches": 1,
                  "collect": False})
        w.forward_delay_s = delay


# ---------------------------------------------------------------------------
# inproc (threads): staleness-0 identity with the serial path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("microbatches", [1, 4])
@pytest.mark.parametrize("merge", ["avg", "concat"])
def test_inproc_matches_protocol_step(merge, microbatches):
    cfg = dataclasses.replace(BANK_MARKETING, merge=merge)
    params, feats, y, loss_fn = _setup(cfg)

    loss_s, tg_s, sg_s, ledger_s = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
    )
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(cfg.num_clients)]
    with InprocTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, merge,
                            microbatches=microbatches)
        res = executor.run_step(params["server"], y, features=feats)

    np.testing.assert_allclose(res.loss, loss_s, atol=1e-5, rtol=1e-5)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s))
    assert res.report.total_misses == 0
    assert res.report.transport == "InprocTransport"
    # same protocol messages as the serial schedule — only the clock moved
    assert res.ledger.total() == ledger_s.total()


def test_inproc_local_updates_train():
    """Workers holding a local optimizer must actually learn: the real
    split-learning flow where tower params never leave the client."""
    cfg = TINY
    batch, steps = 32, 30
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    slices = split_model.feature_slices(cfg)
    idx = [jnp.asarray(s.indices) for s in slices]

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    workers = [
        build_mlp_worker(k, cfg=cfg, param_seed=0, data_seed=0, batch=batch,
                         microbatches=1, learning_rate=0.2)
        for k in range(cfg.num_clients)
    ]
    server = params["server"]
    losses = []
    with InprocTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=1)
        for step in range(steps):
            ks = jax.random.split(jax.random.PRNGKey(step), 2)
            x = jax.random.normal(ks[0], (batch, cfg.input_dim))
            y = (x[:, 0] > 0).astype(jnp.int32)  # learnable rule
            res = executor.run_step(server, y, step=step,
                                    collect_grads=False)
            server = jax.tree_util.tree_map(
                lambda p, g: p - 0.2 * g, server, res.server_grads)
            losses.append(float(res.loss))
    assert sum(losses[-5:]) / 5 < sum(losses[:5]) / 5 - 0.1, losses


def test_inproc_nowait_wallclock_straggler():
    """A client with a real (sleep-injected) slowdown must miss the static
    wall-clock deadline and get EMA-imputed; the healthy majority merges."""
    cfg = TINY3  # healthy majority of 2 around one straggler
    params, feats, y, loss_fn = _setup(cfg)

    # long enough that the straggler's second cut is still in flight when
    # the server reaches microbatch 1 (a cut that arrives while the server
    # is busy elsewhere is NOT late — only deadline-checked on gather).
    # 4s per forward (2nd cut ~8s in) keeps headroom over the server's
    # first-call autodiff tracing, which can run seconds on a loaded CI
    # host mid-suite — at 2s this test flaked when tracing outran the
    # straggler and its queued cut legitimately "beat" the deadline sweep.
    delay = 4.0
    workers = [
        TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                    forward_delay_s=delay if k == 1 else 0.0)
        for k in range(cfg.num_clients)
    ]
    _precompile(workers, feats, 8)
    with InprocTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            mode="nowait", microbatches=2, deadline=0.15)
        res = executor.run_step(params["server"], y, features=feats)

    assert res.report.misses_per_client[1] == 2  # missed both microbatches
    assert sum(res.report.misses_per_client) == 2
    assert np.isfinite(float(res.loss))
    # missed every microbatch -> zero local gradient for the straggler
    for leaf in jax.tree_util.tree_leaves(res.tower_grads[1]):
        np.testing.assert_allclose(leaf, np.zeros_like(leaf))
    assert res.ema_state is not None


def test_inproc_nowait_busy_server_does_not_fabricate_misses():
    """A cut DELIVERED while role 0 was busy on an earlier microbatch beat
    the deadline and must not be imputed: the expired-window path has to
    sweep the response queue before declaring a miss."""
    import time as _time

    cfg = TINY3
    params, feats, y, loss_fn = _setup(cfg)
    slept = []

    def slow_loss(logits, labels):
        # the server stalls >> the deadline on the first microbatch only,
        # long enough for every mb-1 cut to be sitting in the queue
        if not slept:
            slept.append(True)
            _time.sleep(1.0)
        return loss_fn(logits, labels)

    workers = [
        TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                    forward_delay_s=0.05 if k == 1 else 0.0)
        for k in range(cfg.num_clients)
    ]
    _precompile(workers, feats, 8)
    with InprocTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, slow_loss, cfg.merge,
                            mode="nowait", microbatches=2, deadline=0.3)
        res = executor.run_step(params["server"], y, features=feats)
    # client 1 is 0.05s slow — comfortably inside the 0.3s window — and its
    # mb-1 cut lands during the server's mb-0 stall; zero misses either way
    assert res.report.misses_per_client == [0, 0, 0], res.report


def test_fast_merge_lm_shaped_stacks():
    """The merge fast path must accept (K, B, S, D) transformer cut stacks
    (flattened around the (K, B, D) kernel), for reductions AND concat."""
    from repro.core import merge as merge_lib
    from repro.runtime.executor import fast_merge

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 5, 8))
    for strategy in ("avg", "sum", "max", "mul", "concat"):
        got = fast_merge(x, strategy)
        want = merge_lib.merge_stacked(x, strategy)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# multiproc (spawned processes + TCP loopback)
# ---------------------------------------------------------------------------

def test_multiproc_loopback_matches_protocol_and_costs():
    """Real socket loopback: spawned per-role processes regenerate their own
    tower params and feature slices from the shared seeds; gradients must
    match the serial protocol_step to 1e-5 and the per-role Ledger byte
    counts must match the ``core.costs`` analytic traffic model."""
    cfg = TINY
    batch, M = 16, 2

    # the driver-side reference regenerates the same seeded state the
    # children build for themselves (nothing is shipped to them)
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(
        jax.random.split(jax.random.PRNGKey(0), 2)[0], (batch, cfg.input_dim))
    y = jax.random.randint(jax.random.PRNGKey(7), (batch,), 0,
                           cfg.num_classes)
    slices = split_model.feature_slices(cfg)
    feats = [x[:, jnp.asarray(s.indices)] for s in slices]

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    loss_s, tg_s, sg_s, _ = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, cfg.merge,
    )

    specs = [
        WorkerSpec(build_mlp_worker,
                   dict(cfg=cfg, param_seed=0, data_seed=0, batch=batch,
                        microbatches=M))
        for _ in range(cfg.num_clients)
    ]
    with MultiprocTransport(specs) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=M)
        res = executor.run_step(params["server"], y, step=0)
    # close() must not leak children: the shutdown handshake (escalated to
    # terminate/kill for a wedged child) leaves no surviving processes
    assert not any(p.is_alive() for p in tr._procs)

    np.testing.assert_allclose(res.loss, loss_s, atol=1e-5, rtol=1e-5)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s))
    assert res.report.transport == "MultiprocTransport"
    assert res.report.tower_platform == "cpu"

    # per-role byte accounting over the real socket vs the analytic model
    want = costs.epoch_traffic(cfg, num_samples=batch, batch_size=batch)
    ledger = res.ledger
    assert ledger.sent_by("role0") == want["role0"].sent_bytes
    assert ledger.received_by("role0") == want["role0"].received_bytes
    assert ledger.sent_by("role3") == want["role3"].sent_bytes
    assert ledger.received_by("role3") == want["role3"].received_bytes
    assert ledger.sent_by("role1") == want["role1"].sent_bytes * (
        cfg.num_clients - 1)


def test_multiproc_children_pin_cpu_whatever_the_environment(monkeypatch):
    """A spawned child computes on the host CPU even when the inherited
    environment names an accelerator (which the parent would hold), and
    its hello says so."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    specs = [WorkerSpec(build_mlp_worker, dict(cfg=TINY, batch=4))
             for _ in range(TINY.num_clients)]
    with MultiprocTransport(specs) as tr:
        assert tr.tower_platform == "cpu"


# ---------------------------------------------------------------------------
# worker cross-step buffering (delayed-gradient semantics at window W > 1)
# ---------------------------------------------------------------------------

def test_worker_out_of_order_step_buffering():
    """The cross-step FIFO order — step t+1 forwards BEFORE step t's
    backward/finish — must leave every step's state intact: step t+1 feats
    survive finish_step(t), and step t+1's backward linearizes at the param
    snapshot its forward ran under, not at the post-update params."""
    from repro.transport.builders import _sgd

    cfg = TINY
    lr = 0.1
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    p0 = params["towers"][0]
    worker = TowerWorker(0, towers.mlp_tower_apply, p0,
                         optimizer=_sgd(lr))
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    f0 = jax.random.normal(ks[0], (4, 8))
    f1 = jax.random.normal(ks[1], (4, 8))
    j0 = jax.random.normal(ks[2], (4, cfg.cut_dim))
    j1 = jax.random.normal(ks[3], (4, cfg.cut_dim))

    def grad_at(base, feats, jac):
        return jax.grad(lambda tp: jnp.vdot(
            towers.mlp_tower_apply(tp, feats).astype(jnp.float32),
            jac.astype(jnp.float32)))(base)

    r0 = worker.handle({"op": "forward", "step": 0, "mb": 0, "feats": f0})
    # cross-step: step 1's forward arrives before step 0's backward and
    # runs on the SAME (pre-update) params
    r1 = worker.handle({"op": "forward", "step": 1, "mb": 0, "feats": f1})
    np.testing.assert_array_equal(r1["cut"],
                                  towers.mlp_tower_apply(p0, f1))
    worker.handle({"op": "backward", "step": 0, "mb": 0, "jac": j0})
    done0 = worker.handle({"op": "finish_step", "step": 0,
                           "microbatches": 1, "collect": True,
                           "expected_jacs": 1})
    g0 = grad_at(p0, f0, j0)
    _assert_trees_close(done0["grad"], g0, atol=1e-6)
    p1 = jax.tree_util.tree_map(lambda p, g: p - lr * g, p0, g0)
    _assert_trees_close(worker.params, p1, atol=1e-6)

    # step 1's backward must linearize at p0 (its forward's snapshot) even
    # though the worker's live params are already p1
    resp = worker.handle({"op": "backward", "step": 1, "mb": 0, "jac": j1})
    assert resp["op"] == "grad"
    done1 = worker.handle({"op": "finish_step", "step": 1,
                           "microbatches": 1, "collect": True,
                           "expected_jacs": 1})
    g1 = grad_at(p0, f1, j1)
    _assert_trees_close(done1["grad"], g1, atol=1e-6)
    # ...and the update applies to the CURRENT params (p1), not the snapshot
    p2 = jax.tree_util.tree_map(lambda p, g: p - lr * g, p1, g1)
    _assert_trees_close(worker.params, p2, atol=1e-6)
    assert not worker._feats and not worker._step_params


def test_worker_defers_finish_until_jacobians_land():
    """A finish_step carrying expected_jacs > seen backwards defers the
    optimizer update; the completing backward returns the step_done (a
    non-FIFO transport can reorder the two without corrupting the step)."""
    cfg = TINY
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    worker = TowerWorker(0, towers.mlp_tower_apply, params["towers"][0])
    f0 = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    j0 = jax.random.normal(jax.random.PRNGKey(2), (4, cfg.cut_dim))

    worker.handle({"op": "forward", "step": 0, "mb": 0, "feats": f0})
    assert worker.handle({"op": "finish_step", "step": 0, "microbatches": 1,
                          "collect": True, "expected_jacs": 1}) is None
    resp = worker.handle({"op": "backward", "step": 0, "mb": 0, "jac": j0})
    assert resp["op"] == "step_done" and resp["step"] == 0
    g0 = jax.grad(lambda tp: jnp.vdot(
        towers.mlp_tower_apply(tp, f0).astype(jnp.float32),
        j0.astype(jnp.float32)))(params["towers"][0])
    _assert_trees_close(resp["grad"], g0, atol=1e-6)


@pytest.mark.parametrize("case", ["steady", "new_shape", "matches_eager"])
def test_worker_compiles_each_program_once_per_shape(case):
    """The worker's forward, backward and local AdamW update are compiled
    programs: each is traced once per input shape and reused every step
    after, and what they compute is what an eager ``jax.grad`` of the same
    f32 vdot objective and an eager update give."""
    from repro.optim import AdamW

    cfg = TINY
    params, feats, y, loss_fn = _setup(cfg)
    opt = AdamW(learning_rate=1e-2, weight_decay=0.1, grad_clip_norm=1.0)
    once = {"forward": 1, "backward": 1, "update": 1}

    if case == "matches_eager":
        p = params["towers"][0]
        worker = TowerWorker(0, towers.mlp_tower_apply, p, optimizer=opt)
        state = opt.init(p)
        for step in range(2):
            ks = jax.random.split(jax.random.PRNGKey(10 + step), 2)
            f = jax.random.normal(ks[0], (16, 8))
            jac = jax.random.normal(ks[1], (16, cfg.cut_dim))
            cut = worker.handle({"op": "forward", "step": step, "mb": 0,
                                 "feats": f})["cut"]
            worker.handle({"op": "backward", "step": step, "mb": 0,
                           "jac": jac})
            done = worker.handle({"op": "finish_step", "step": step,
                                  "microbatches": 1, "collect": True})
            grad = jax.grad(lambda tp: jnp.vdot(
                towers.mlp_tower_apply(tp, f).astype(jnp.float32),
                jac.astype(jnp.float32)))(p)
            want = (towers.mlp_tower_apply(p, f), grad)
            p, state = opt.update(p, grad, state)
            for got, ref in zip(jax.tree_util.tree_leaves(
                    (cut, done["grad"], worker.params)),
                    jax.tree_util.tree_leaves(want + (p,))):
                np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)
        assert worker.traces == once
        return

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                           optimizer=opt)
               for k in range(cfg.num_clients)]
    with InprocTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=1)
        for step in range(4):
            executor.run_step(params["server"], y, step=step,
                              features=feats, collect_grads=False)
            assert [w.traces for w in workers] == [once] * cfg.num_clients
        if case == "new_shape":
            # half the rows: forward and backward see a new shape, the
            # update's params and gradients do not
            executor.run_step(params["server"], y[:8], step=4,
                              features=[f[:8] for f in feats],
                              collect_grads=False)
            assert [w.traces for w in workers] == [
                {"forward": 2, "backward": 2, "update": 1}] * cfg.num_clients


# ---------------------------------------------------------------------------
# adaptive deadline controller
# ---------------------------------------------------------------------------

def test_adaptive_deadline_tightens_and_recovers():
    ctl = AdaptiveDeadline(4, initial_s=1.0, decay=0.5)
    # nothing observed yet: fall back to the initial window
    assert ctl.deadline_s() == 1.0
    # healthy cluster with small spreads -> deadline tightens to the floor
    for _ in range(4):
        for k in range(3):
            ctl.observe(k, 0.01 * (k + 1))
        ctl.observe(3, 5.0)  # 5s straggler, excluded from the max
    d_tight = ctl.deadline_s()
    assert d_tight < 1.0
    assert d_tight >= ctl.floor_frac * 1.0 - 1e-9
    # straggler recovers -> its EWMA decays into the healthy set and the
    # deadline loosens to cover it again
    for _ in range(20):
        for k in range(3):
            ctl.observe(k, 0.01 * (k + 1))
        ctl.observe(3, 0.4)
    d_loose = ctl.deadline_s()
    assert d_loose > d_tight
    assert d_loose >= 0.4  # the recovered client now fits the window
    # never beyond the staleness ceiling
    assert d_loose <= ctl.ceiling_frac * 1.0


def test_nowait_busy_server_clamps_deadline_observations():
    """Satellite fix: a cut swept from the queue AFTER the deadline window
    expired (or while role 0 was busy on an earlier microbatch) is observed
    at its DRAIN time, which can include arbitrary server stall — the
    observation must be clamped to the deadline window so a busy role 0
    cannot inflate the arrival EWMAs and loosen the deadline for no client
    reason."""
    import time as _time

    cfg = TINY3
    params, feats, y, loss_fn = _setup(cfg)
    slept = []

    def slow_loss(logits, labels):
        # role 0 stalls 1.2s on microbatch 0 only — every mb-1 cut is
        # delivered to the queue during the stall and drained late
        if not slept:
            slept.append(True)
            _time.sleep(1.2)
        return loss_fn(logits, labels)

    # staggered but all comfortably inside the window — including its FLOOR
    # (floor_frac * initial = 0.175s), since the healthy cluster's small
    # spreads tighten the adaptive window there immediately
    delays = [0.0, 0.05, 0.1]
    workers = [
        TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                    forward_delay_s=delays[k])
        for k in range(cfg.num_clients)
    ]
    _precompile(workers, feats, 8)  # so sleeps dominate timing
    ctl = AdaptiveDeadline(cfg.num_clients, initial_s=0.35)
    with InprocTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, slow_loss, cfg.merge,
                            mode="nowait", microbatches=2, deadline=ctl)
        res = executor.run_step(params["server"], y, features=feats)

    assert res.report.misses_per_client == [0, 0, 0], res.report
    # without the clamp, client 1/2's mb-1 observations would be ~>1s
    # (drain time after the stall); with it every EWMA stays within the
    # window the cuts actually beat
    for spread in ctl.spreads():
        assert spread is not None and spread <= 0.35 + 1e-6, ctl.spreads()


def test_nowait_recovered_straggler_rejoins_merges():
    """Late-arrival loosening end-to-end: a straggler missing the window
    still has its (late) arrivals observed — raw, unclamped — so when it
    recovers, its decaying EWMA re-enters the healthy set and it starts
    making merges again instead of being imputed forever."""
    cfg = TINY3
    params, feats, y, loss_fn = _setup(cfg)
    delay = 0.8
    workers = [
        TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                    forward_delay_s=delay if k == 2 else 0.0)
        for k in range(cfg.num_clients)
    ]
    _precompile(workers, feats, len(y))  # so sleeps dominate timing
    ctl = AdaptiveDeadline(cfg.num_clients, initial_s=0.2, decay=0.3)
    with InprocTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            mode="nowait", microbatches=1, deadline=ctl)
        ema_state = None
        sick_misses = 0
        for step in range(3):
            res = executor.run_step(params["server"], y, step=step,
                                    features=feats, ema_state=ema_state,
                                    collect_grads=False)
            ema_state = res.ema_state
            sick_misses += res.report.misses_per_client[2]
        assert sick_misses >= 2  # it really was missing merges
        # the late cuts were observed raw: the EWMA reflects true lateness
        assert ctl.spreads()[2] > 0.3
        # straggler recovers
        workers[2].forward_delay_s = 0.0
        healed_misses = 0
        for step in range(3, 8):
            res = executor.run_step(params["server"], y, step=step,
                                    features=feats, ema_state=ema_state,
                                    collect_grads=False)
            ema_state = res.ema_state
            healed_misses += res.report.misses_per_client[2]
        assert res.report.misses_per_client[2] == 0  # back in the merge
        assert healed_misses <= 3  # rejoined within a few steps


def test_adaptive_deadline_late_arrival_loosens_window():
    """Controller-level late-arrival loosening: a recovered straggler's
    moderate spreads must re-open the deadline window far enough to cover
    it (the loosening direction of the EWMA policy)."""
    ctl = AdaptiveDeadline(3, initial_s=0.8, decay=0.5)
    # healthy start: window tightens toward the floor
    for _ in range(6):
        for k in range(3):
            ctl.observe(k, 0.01)
    tight = ctl.deadline_s()
    assert tight < 0.8
    # client 2 turns into a moderate laggard (late arrivals observed after
    # its merges are missed); its EWMA stays within the healthy cut so the
    # window must LOOSEN to cover it again
    for _ in range(10):
        ctl.observe(0, 0.01)
        ctl.observe(1, 0.012)
        ctl.observe(2, 0.3)
    loose = ctl.deadline_s()
    assert loose > tight
    assert loose >= 0.3  # the window re-opened over the laggard
    assert loose <= ctl.ceiling_frac * 0.8


def test_adaptive_deadline_seed_from_observations():
    ctl = AdaptiveDeadline(3)
    assert ctl.deadline_s() is None  # bootstrap barrier: wait for everyone
    ctl.observe(0, 0.0)
    ctl.observe(1, 0.002)
    ctl.observe(2, 2.0)  # straggler in the barrier
    ctl.seed_from_observations()
    # the median anchoring keeps the straggler out of the baseline
    assert ctl.initial_s < 1.0
    assert ctl.deadline_s() is not None


# ---------------------------------------------------------------------------
# SimTransport parity (the wrapper backend used by protocol/pipelined_step)
# ---------------------------------------------------------------------------

def test_sim_transport_matches_inproc():
    cfg = TINY
    params, feats, y, loss_fn = _setup(cfg, batch=8)

    def run(transport_cls):
        workers = [TowerWorker(k, towers.mlp_tower_apply,
                               params["towers"][k])
                   for k in range(cfg.num_clients)]
        tr = transport_cls(workers)
        try:
            executor = Executor(tr, towers.mlp_tower_apply, loss_fn,
                                cfg.merge, microbatches=2)
            return executor.run_step(params["server"], y, features=feats)
        finally:
            tr.close()

    a, b = run(SimTransport), run(InprocTransport)
    np.testing.assert_allclose(a.loss, b.loss, atol=1e-6)
    _assert_trees_close((a.tower_grads, a.server_grads),
                        (b.tower_grads, b.server_grads), atol=1e-6)
    assert a.ledger.total() == b.ledger.total()


# ---------------------------------------------------------------------------
# family-parametrized SplitProgram equivalence: every family's step-0 split
# gradients over Sim/Inproc transports match the serial protocol_step
# ---------------------------------------------------------------------------

FAMILY_ARCHS = [
    ("dense", "smollm-360m"),
    ("ssm", "mamba2-1.3b"),
    ("hybrid", "zamba2-7b"),
    ("moe", "deepseek-moe-16b"),
    ("audio", "whisper-tiny"),
    ("vlm", "internvl2-26b"),
]


def _family_setup(arch, batch=2, seq=16, seed=0):
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.models import backbone, split_program

    cfg = get_arch(arch).reduced()
    program = split_program.get_program(cfg)
    params = backbone.init_params(cfg, jax.random.PRNGKey(seed))
    towers_p, server_p = program.partition(params)
    b = {k: jnp.asarray(v) for k, v in
         LMBatchLoader(cfg, batch, seq, seed=seed).next_batch().items()}
    return cfg, program, towers_p, server_p, b


@pytest.mark.parametrize("family,arch", FAMILY_ARCHS)
def test_family_split_gradients_match_serial_protocol(family, arch):
    """The §3 identity per family: the program's decomposition over a real
    (threaded) transport and the inline SimTransport both reproduce the
    serial ``protocol_step`` loss/gradients to 1e-5, with identical ledger
    bytes — and only aux-carrying families record the ``aux_loss`` slot."""
    cfg, program, towers_p, server_p, b = _family_setup(arch)
    assert cfg.family == family
    feats, ctx = program.features(b), program.batch_ctx(b)
    loss_s, tg_s, sg_s, ledger_s = program.protocol_step(
        towers_p, server_p, feats, ctx)

    for transport_cls in (SimTransport, InprocTransport):
        workers = [TowerWorker(k, program.tower_fwd(k), towers_p[k])
                   for k in range(program.num_clients)]
        tr = transport_cls(workers)
        try:
            executor = Executor(tr, program.server_fwd, program.loss_fn,
                                program.merge,
                                microbatches=1, **program.executor_kwargs)
            res = executor.run_step(server_p, ctx, features=feats)
        finally:
            tr.close()
        np.testing.assert_allclose(res.loss, loss_s, atol=1e-5, rtol=1e-5)
        _assert_trees_close((res.tower_grads, res.server_grads),
                            (tg_s, sg_s))
        assert res.ledger.total() == ledger_s.total()
        assert ((res.ledger.bytes_with_tag("aux_loss") > 0)
                == program.has_aux)
        if program.has_aux:
            assert res.aux is not None and float(res.aux) > 0
        else:
            assert res.aux is None


@pytest.mark.parametrize("arch", ["whisper-tiny", "internvl2-26b"])
def test_modality_workers_regenerate_features_from_seed(arch):
    """Audio/vlm workers built by ``build_split_worker`` own their feature
    source (mel-band frame slices / modality inputs regenerated from the
    shared loader seed) — no feature tensors cross the transport, and the
    gradients still match the serial reference."""
    from repro.transport import build_split_worker

    cfg, program, towers_p, server_p, b = _family_setup(arch)
    feats, ctx = program.features(b), program.batch_ctx(b)
    loss_s, tg_s, sg_s, _ = program.protocol_step(
        towers_p, server_p, feats, ctx)

    workers = [build_split_worker(k, cfg=cfg, seed=0, batch=2, seq=16)
               for k in range(program.num_clients)]
    with InprocTransport(workers) as tr:
        executor = Executor(tr, program.server_fwd, program.loss_fn,
                            program.merge, microbatches=1,
                            **program.executor_kwargs)
        res = executor.run_step(server_p, ctx, step=0)  # workers own feats

    np.testing.assert_allclose(res.loss, loss_s, atol=1e-5, rtol=1e-5)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s))


def test_moe_aux_loss_survives_exchange_and_reconciles():
    """The moe router aux loss must ride the role-0 -> role-3 exchange (not
    be silently dropped): nonzero aux in the result, one f32 scalar per
    microbatch on the ledger's ``aux_loss`` tag, and role 3's received
    bytes reconcile with the analytic ``costs`` model."""
    cfg, program, towers_p, server_p, b = _family_setup(
        "deepseek-moe-16b", batch=4)
    assert program.has_aux
    feats, ctx = program.features(b), program.batch_ctx(b)
    M = 2

    workers = [TowerWorker(k, program.tower_fwd(k), towers_p[k])
               for k in range(program.num_clients)]
    with InprocTransport(workers) as tr:
        executor = Executor(tr, program.server_fwd, program.loss_fn,
                            program.merge, microbatches=M,
                            **program.executor_kwargs)
        res = executor.run_step(server_p, ctx, features=feats)

    assert res.aux is not None and float(res.aux) > 0
    aux_bytes = costs.aux_exchange_bytes(M)
    assert res.ledger.bytes_with_tag("aux_loss") == aux_bytes
    # role 3 receives: the head outputs, its own jacobian downlink, and the
    # aux scalar — nothing else
    want_recv = (res.ledger.bytes_with_tag("head_output")
                 + res.ledger.bytes_with_tag("jac[0]") + aux_bytes)
    assert res.ledger.received_by("role3") == want_recv
    # microbatched pipelining == the mean of per-microbatch serial steps
    # (the router density estimate is per-merge, so the M=2 reference is
    # two half-batch protocol steps, not one full-batch step)
    mbsz = 4 // M
    ref_losses = []
    for m in range(M):
        sl = slice(m * mbsz, (m + 1) * mbsz)
        loss_m, _, _, _ = program.protocol_step(
            towers_p, server_p, [f[sl] for f in feats], ctx[sl])
        ref_losses.append(loss_m)
    np.testing.assert_allclose(res.loss, sum(ref_losses) / M,
                               atol=1e-5, rtol=1e-5)


def test_epoch_traffic_aux_slot():
    """The analytic model's aux slot: one f32 scalar per batch, role 0 ->
    role 3, matching ``aux_exchange_bytes``."""
    base = costs.epoch_traffic(TINY, num_samples=32, batch_size=16)
    with_aux = costs.epoch_traffic(TINY, num_samples=32, batch_size=16,
                                   aux_loss=True)
    per_batch = costs.aux_exchange_bytes(1)
    assert (with_aux["role0"].sent_bytes - base["role0"].sent_bytes
            == 2 * per_batch)
    assert (with_aux["role3"].received_bytes - base["role3"].received_bytes
            == 2 * per_batch)
    assert with_aux["role1"] == base["role1"]
