"""Pipelined runtime: §3 identity at staleness 0, byte accounting against
the analytic collective model, straggler no-wait behavior, and the
simulated-clock win over the serial schedule."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vertical_mlp import BANK_MARKETING, FINANCIAL_PHRASEBANK
from repro.core import protocol, split_model, towers
from repro.core.merge import collective_bytes_per_merge
from repro.runtime import (
    LinkModel,
    default_deadline_s,
    pipelined_step,
    plan_step,
    simulate_pipelined,
    simulate_serial,
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
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=1e-4)


# ---------------------------------------------------------------------------
# §3 identity: pipelined @ staleness 0 == protocol_step == monolithic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("microbatches", [1, 4])
@pytest.mark.parametrize("merge", ["sum", "avg", "max", "concat", "mul"])
def test_pipelined_staleness0_equals_protocol_step(merge, microbatches):
    cfg = dataclasses.replace(BANK_MARKETING, merge=merge)
    params, feats, y, loss_fn = _setup(cfg)

    loss_s, tg_s, sg_s, _ = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
    )
    loss_p, tg_p, sg_p, _, report, _ = pipelined_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
        microbatches=microbatches,
        plan=plan_step(cfg, 16, microbatches),
        link=LinkModel.uniform(cfg.num_clients),
    )
    np.testing.assert_allclose(loss_p, loss_s, atol=1e-5, rtol=1e-5)
    _assert_trees_close((tg_p, sg_p), (tg_s, sg_s))
    assert report.total_misses == 0  # staleness 0: nobody imputed

    # ... and protocol_step itself == monolithic backprop (transitively the
    # pipelined path reproduces end-to-end autodiff)
    protocol.assert_equivalent_to_monolithic(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
    )


# ---------------------------------------------------------------------------
# byte accounting: ledger vs the analytic collective model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge", ["sum", "avg", "max", "concat", "mul"])
def test_ledger_vs_collective_bytes(merge):
    cfg = dataclasses.replace(BANK_MARKETING, merge=merge)
    B, M = 16, 4
    params, feats, y, loss_fn = _setup(cfg, batch=B)
    plan = plan_step(cfg, B, M)

    _, _, _, ledger, report, _ = pipelined_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
        microbatches=M, plan=plan, link=LinkModel.uniform(cfg.num_clients),
    )
    # every client uplinks cut_dim floats per sample, M microbatches a step
    per_client = [
        ledger.bytes_with_tag(f"cut[{k}]") for k in range(cfg.num_clients)
    ]
    assert per_client == [B * cfg.cut_dim * 4] * cfg.num_clients
    assert report.cut_bytes_per_client == per_client[0]

    # the engine's analytic collective figure must agree with costs.py's
    # model applied to the ledger-observed payload
    payload_elements = per_client[0] // (4 * M)  # per microbatch
    want = M * collective_bytes_per_merge(
        merge, payload_elements, cfg.num_clients, 4
    )
    assert report.collective_bytes_per_client == want

    # pipelined and serial schedules move identical bytes — same messages,
    # different clock
    _, _, _, serial_ledger = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
    )
    assert ledger.total() == serial_ledger.total()
    assert ledger.sent_by("role0") == serial_ledger.sent_by("role0")


# ---------------------------------------------------------------------------
# clock: pipelining must beat the serial schedule
# ---------------------------------------------------------------------------

def test_pipelined_step_time_beats_serial_at_k4():
    """The acceptance bar: >= 1.5x at K=4 under the same link cost model."""
    cfg = dataclasses.replace(FINANCIAL_PHRASEBANK, merge="avg")
    plan = plan_step(cfg, batch_size=256, microbatches=4)
    link = LinkModel.uniform(cfg.num_clients)
    serial = simulate_serial(plan, link)
    pipe = simulate_pipelined(plan, link, mode="pipelined")
    assert serial.step_time_s / pipe.step_time_s >= 1.5


def test_nowait_bounds_straggler_step_time():
    cfg = dataclasses.replace(FINANCIAL_PHRASEBANK, merge="avg")
    plan = plan_step(cfg, batch_size=256, microbatches=4)
    link = LinkModel.uniform(cfg.num_clients).with_straggler(2, slowdown=10.0)
    wait = simulate_pipelined(plan, link, mode="pipelined")
    nowait = simulate_pipelined(plan, link, mode="nowait")
    assert nowait.misses_per_client[2] > 0  # the straggler gets imputed
    assert sum(nowait.misses_per_client) == nowait.misses_per_client[2]
    assert nowait.step_time_s < 0.5 * wait.step_time_s


def test_adaptive_deadline_tightens_in_simulation():
    """With no explicit deadline, the EWMA controller drives the no-wait
    window: after the first microbatch it tightens below the static
    default (the straggler is excluded from the healthy max), so the
    adaptive step can only be as fast or faster — with the same misses."""
    from repro.runtime import AdaptiveDeadline

    cfg = dataclasses.replace(FINANCIAL_PHRASEBANK, merge="avg")
    plan = plan_step(cfg, batch_size=512, microbatches=8)
    link = LinkModel.uniform(cfg.num_clients).with_straggler(2, slowdown=10.0)

    static = simulate_pipelined(
        plan, link, mode="nowait",
        deadline_s=default_deadline_s(plan, link))
    ctl = AdaptiveDeadline(
        cfg.num_clients, initial_s=default_deadline_s(plan, link))
    adaptive = simulate_pipelined(plan, link, mode="nowait", deadline=ctl)

    # the straggler misses essentially every merge; a healthy client may
    # lose at most one early microbatch while the EWMAs are still learning
    # the uplink-contention spread (no-wait imputes it — that is the deal)
    assert adaptive.misses_per_client[2] >= plan.microbatches - 1
    healthy_misses = sum(adaptive.misses_per_client) - adaptive.misses_per_client[2]
    assert healthy_misses <= 1
    assert adaptive.step_time_s <= static.step_time_s + 1e-9
    # the controller actually learned the federation: every client observed,
    # the straggler's EWMA well above the healthy cluster
    spreads = ctl.spreads()
    assert all(s is not None for s in spreads)
    healthy = [s for k, s in enumerate(spreads) if k != 2]
    assert spreads[2] > 10 * max(healthy)


def test_deadline_default_is_fastest_path():
    cfg = dataclasses.replace(BANK_MARKETING, merge="avg")
    plan = plan_step(cfg, 16, 2)
    link = LinkModel.uniform(cfg.num_clients)
    d = default_deadline_s(plan, link)
    assert d > 0
    # uniform clients all arrive together: no misses even in nowait mode
    rep = simulate_pipelined(plan, link, mode="nowait")
    assert rep.total_misses == 0


# ---------------------------------------------------------------------------
# no-wait convergence smoke under heavy dropping
# ---------------------------------------------------------------------------

def test_nowait_convergence_smoke():
    """With one client 20x degraded (missing every deadline), no-wait
    training must still drive the loss down — the EMA imputation keeps the
    merged representation sane while the stragglers sit out."""
    cfg = dataclasses.replace(FINANCIAL_PHRASEBANK, merge="avg")
    B, M, steps, lr = 32, 4, 40, 0.2
    key = jax.random.PRNGKey(0)
    params = split_model.init_split_mlp(key, cfg)
    plan = plan_step(cfg, B, M)
    link = LinkModel.uniform(cfg.num_clients).with_straggler(1, slowdown=20.0)

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    slices = split_model.feature_slices(cfg)
    idx = [jnp.asarray(s.indices) for s in slices]
    ema_state = None
    losses = []
    for step in range(steps):
        ks = jax.random.split(jax.random.PRNGKey(step + 1), 2)
        x = jax.random.normal(ks[0], (B, cfg.input_dim))
        # learnable rule: label = sign of the first feature of client 0
        y = (x[:, 0] > 0).astype(jnp.int32)
        feats = [x[:, i] for i in idx]
        loss, tg, sg, _, report, ema_state = pipelined_step(
            towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
            params["towers"], params["server"], feats, y, cfg.merge,
            microbatches=M, mode="nowait", plan=plan, link=link,
            ema_state=ema_state,
        )
        assert report.misses_per_client[1] == M  # straggler misses every mb
        params = {
            "towers": [
                jax.tree_util.tree_map(lambda p, g: p - lr * g, tp, g)
                for tp, g in zip(params["towers"], tg)
            ],
            "server": jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params["server"], sg
            ),
        }
        losses.append(float(loss))
    first = sum(losses[:5]) / 5
    last = sum(losses[-5:]) / 5
    assert last < first - 0.1, (first, last)


# ---------------------------------------------------------------------------
# runtime-aware placement: the advisor clocked on the pipelined schedule
# ---------------------------------------------------------------------------

def test_advise_split_depth_objectives_can_disagree():
    """The serial clock pays every client tower one after another (depth is
    K-times-expensive), while the pipelined clock runs towers in parallel
    and serializes only the shared role-0 server — so the two objectives
    legitimately pick different placements of the same hidden stack."""
    from repro.configs.vertical_mlp import MLPSplitConfig
    from repro.core.costs import advise_split_depth

    cfg = MLPSplitConfig(
        name="advisor_sweep", input_dim=32, num_classes=2, num_clients=4,
        client_feature_sizes=(8, 8, 8, 8), tower_hidden=(512,), cut_dim=512,
        server_hidden=(512, 512), merge="avg",
    )
    kw = dict(bandwidth_bytes_per_s=1e12, client_flops_per_s=1e9,
              server_flops_per_s=1e9, batch_size=32, microbatches=4)
    serial = advise_split_depth(cfg, objective="serial", **kw)
    pipelined = advise_split_depth(cfg, objective="pipelined", **kw)

    # serial: every tower layer is paid K times sequentially -> stay thin
    assert serial["recommended_tower_layers"] == 1
    # pipelined: parallel towers unload the serialized server -> go deeper
    assert pipelined["recommended_tower_layers"] > 1
    assert (serial["recommended_tower_layers"]
            != pipelined["recommended_tower_layers"])
    # both sweeps cover the same candidate placements of the 3-layer stack
    assert (set(serial["step_time_s_by_depth"])
            == set(pipelined["step_time_s_by_depth"]) == {1, 2, 3})
    # the simulated objective really is the simulate_* clock
    for r in (serial, pipelined):
        d = r["recommended_tower_layers"]
        assert r["step_time_s_by_depth"][d] == min(
            r["step_time_s_by_depth"].values())


def test_advise_split_depth_heuristic_unchanged():
    """objective='heuristic' keeps the paper-§4.4 rule verbatim (the
    comm-vs-compute binary), so existing guidance tests keep their
    meaning."""
    from repro.configs.vertical_mlp import BANK_MARKETING
    from repro.core.costs import advise_split_depth

    r = advise_split_depth(
        BANK_MARKETING, bandwidth_bytes_per_s=1e4, client_flops_per_s=1e12,
        server_flops_per_s=1e13,
    )
    assert r["objective"] == "heuristic"
    assert r["comm_bound"] and r["recommended_tower_layers"] > 1


# ---------------------------------------------------------------------------
# the executor's merge, chosen once per topology and mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case,calls,jacs", [
    ("barrier", [("fast_merge", "avg")], 3),
    ("masked", [("merge_stacked", "avg")], 3),
    ("nowait", [("impute_stack", None), ("fast_merge", "avg")], 2),
    ("tree", [("fast_merge", "sum")], 3),
])
def test_executor_merge_choice(monkeypatch, case, calls, jacs):
    """Which merge runs: the fused kernel for a barrier step, merge_stacked
    under a mask (every client still gets a jacobian), EMA imputation then
    the fused kernel under no-wait (the dropped client gets none), and the
    tree's final sum over the top-level partial sums."""
    from repro.configs.vertical_mlp import MLPSplitConfig
    from repro.core import merge, straggler
    from repro.runtime import executor as executor_mod
    from repro.runtime.topology import AggTree
    from repro.transport import SimTransport, TowerWorker

    cfg = MLPSplitConfig(
        name="merge_choice", input_dim=12, num_classes=2, num_clients=3,
        client_feature_sizes=(4, 4, 4), tower_hidden=(8,), cut_dim=8,
        server_hidden=(8,), merge="avg")
    params, feats, y, loss_fn = _setup(cfg, batch=8)
    seen = []

    def counting(name, fn, strategy_of):
        def wrapped(*args, **kwargs):
            seen.append((name, strategy_of(args)))
            return fn(*args, **kwargs)
        return wrapped

    # the executor's own references only: the kernel's CPU oracle calls
    # merge_stacked too
    monkeypatch.setattr(executor_mod, "fast_merge", counting(
        "fast_merge", executor_mod.fast_merge, lambda a: a[1]))
    monkeypatch.setattr(executor_mod, "merge_lib", types.SimpleNamespace(
        merge_stacked=counting("merge_stacked", merge.merge_stacked,
                               lambda a: a[1])))
    monkeypatch.setattr(executor_mod, "straggler_lib", types.SimpleNamespace(
        impute_stack=counting("impute_stack", straggler.impute_stack,
                              lambda a: None)))

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(cfg.num_clients)]
    ex = executor_mod.Executor(
        SimTransport(workers), towers.mlp_tower_apply, loss_fn, cfg.merge,
        mode="nowait" if case == "nowait" else "serial",
        agg_tree=AggTree(3, fanout=2) if case == "tree" else None)
    try:
        res = ex.run_step(
            params["server"], y, features=feats,
            merge_mask=jnp.asarray([1.0, 0.0, 1.0]) if case == "masked"
            else None,
            liveness=[[1, 0, 1]] if case == "nowait" else None)
    finally:
        ex.transport.close()
    assert seen == calls
    assert sum(m.tag.startswith(("jac", "tree_jac"))
               for m in res.ledger.messages) == jacs
    assert (res.ema_state is not None) == (case == "nowait")
