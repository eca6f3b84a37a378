"""Hierarchical aggregation: the fanout-F cut-merge tree + jacobian fan-out
(``runtime.topology.AggTree`` + ``transport.tree.TreeRouter`` + the
executor's tree mode) that breaks the role-0 O(K) star wall.

* tree structure and the breadth-first layout invariants;
* schedule re-routing (``tree_cut[l]``/``tree_jac[l]`` tags) and the
  ledger-vs-``costs.tree_cut_bytes`` per-level byte reconciliation —
  role 0 receives only the ``min(F, K)`` top-level frames;
* gradient equivalence vs the flat serial ``protocol_step`` for sum and
  avg at W=1 and W=2 (to ``TREE_VERIFY_ATOL`` — the tree REASSOCIATES the
  f32 merge, so bit-exactness is provably unattainable and the tolerance
  is the documented contract), and composed with secure aggregation;
* relay-worker semantics: out-of-order parts across adjacent in-flight
  steps, fixed deterministic accumulation order, duplicate-part rejection;
* response-pump routing over a real threaded transport with a lagging
  child, and the wedged-relay ``close()`` escalation on MultiprocTransport;
* loud rejection of every unsound combination (non-additive merges,
  merge_fn, compression, no-wait) at construction — never a silent
  wrong-number run;
* the engine's tree clock: serial shows no win, the pipelined clock with a
  finite role-0 NIC shows the O(K) -> O(F) crossover.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vertical_mlp import MLPSplitConfig
from repro.core import costs, protocol, split_model, towers
from repro.runtime import LinkModel, StepPipeline, simulate_pipelined, \
    simulate_serial
from repro.runtime.engine import StepPlan, plan_step
from repro.runtime.executor import Executor
from repro.runtime.topology import TREE_VERIFY_ATOL, AggTree
from repro.transport import (InprocTransport, MultiprocTransport,
                             SimTransport, TowerWorker, TreeRouter,
                             WorkerSpec, build_mlp_worker)

K8 = MLPSplitConfig(
    name="tree_k8", input_dim=16, num_classes=2, num_clients=8,
    client_feature_sizes=(2,) * 8, tower_hidden=(16,), cut_dim=8,
    server_hidden=(16,), merge="sum",
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


def _assert_trees_close(a, b, atol=TREE_VERIFY_ATOL):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=1e-4)


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------

def test_aggtree_k8_f2_layout():
    t = AggTree(num_clients=8, fanout=2)
    assert t.top_level == (0, 1)
    assert t.children(0) == (2, 3) and t.children(1) == (4, 5)
    assert t.children(2) == (6, 7) and t.children(3) == ()
    assert t.parent(0) is None and t.parent(1) is None
    assert t.parent(2) == 0 and t.parent(5) == 1 and t.parent(7) == 2
    assert t.relays == (0, 1, 2)
    assert t.leaves == (3, 4, 5, 6, 7)
    assert t.subtree(0) == (0, 2, 6, 7, 3)
    assert t.subtree(1) == (1, 4, 5)
    assert t.depth == 3
    assert t.edges_at_level(0) == (0, 1)
    assert t.edges_at_level(1) == (2, 3, 4, 5)
    assert t.edges_at_level(2) == (6, 7)
    assert not t.is_star
    # every client appears in exactly one top-level subtree
    covered = sorted(sum((t.subtree(r) for r in t.top_level), ()))
    assert covered == list(range(8))
    # parents have smaller ids (relay FIFO safety)
    for k in range(8):
        p = t.parent(k)
        assert p is None or p < k


def test_aggtree_star_degenerate_and_validation():
    star = AggTree(num_clients=3, fanout=4)
    assert star.is_star and star.relays == () and star.depth == 1
    assert star.top_level == (0, 1, 2)
    with pytest.raises(ValueError, match="fanout must be >= 2"):
        AggTree(num_clients=4, fanout=1)
    with pytest.raises(ValueError, match="num_clients"):
        AggTree(num_clients=0, fanout=2)
    with pytest.raises(ValueError, match="out of range"):
        AggTree(num_clients=4, fanout=2).parent(4)


# ---------------------------------------------------------------------------
# schedule re-routing + byte model
# ---------------------------------------------------------------------------

def test_tree_schedule_tags_and_hops():
    tree = AggTree(num_clients=8, fanout=2)
    sched = protocol.step_schedule(8, tree=tree)
    for k in range(8):
        lvl = tree.edge_level(k)
        assert sched.cuts[k].tag == f"tree_cut[{lvl}]"
        assert sched.jacs[k].tag == f"tree_jac[{lvl}]"
        p = tree.parent(k)
        want_recv = "role0" if p is None else ("role3" if p == 0 else "role1")
        assert sched.cuts[k].receiver == want_recv
        assert sched.jacs[k].sender == want_recv
    with pytest.raises(ValueError, match="cannot compose"):
        protocol.step_schedule(8, tree=tree, compress="topk")
    with pytest.raises(ValueError, match="tree covers"):
        protocol.step_schedule(4, tree=tree)


def test_tree_cut_bytes_model():
    tree = AggTree(num_clients=8, fanout=2)
    got = costs.tree_cut_bytes(tree, cut_bytes=100, microbatches=2)
    assert got["cut_bytes_per_level"] == {0: 2 * 200, 1: 4 * 200, 2: 2 * 200}
    assert got["jac_bytes_per_level"] == got["cut_bytes_per_level"]
    # role 0 pays min(F, K) frames, the star pays K — the headline
    assert got["role0_received"] == got["role0_sent"] == 2 * 200
    assert got["star_role0_received"] == 8 * 200
    # total wire bytes stay K frames per direction: the tree moves WHERE
    # the merge happens, not how much crosses the network
    assert got["total_cut_bytes"] == 8 * 200


def test_tree_ledger_reconciles_with_costs_per_level():
    cfg, M, batch = K8, 2, 16
    params, feats, y, loss_fn = _setup(cfg, batch=batch)
    tree = AggTree(num_clients=8, fanout=2)
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(8)]
    tr = SimTransport(workers)
    try:
        ex = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                      microbatches=M, agg_tree=tree)
        res = ex.run_step(params["server"], y, features=feats)
    finally:
        ex.transport.close()
    cut_bytes = (batch // M) * cfg.cut_dim * 4
    want = costs.tree_cut_bytes(tree, cut_bytes, microbatches=M)
    for lvl in range(tree.depth):
        assert res.ledger.bytes_with_tag(f"tree_cut[{lvl}]") == \
            want["cut_bytes_per_level"][lvl]
        assert res.ledger.bytes_with_tag(f"tree_jac[{lvl}]") == \
            want["jac_bytes_per_level"][lvl]
    # role 0's cut inbox is the level-0 frames only — min(F, K), not K
    assert res.ledger.bytes_with_tag("tree_cut[0]") == \
        want["role0_received"] < want["star_role0_received"]
    # no star tags leak through
    assert all(res.ledger.bytes_with_tag(f"cut[{k}]") == 0 for k in range(8))


# ---------------------------------------------------------------------------
# gradient equivalence vs the flat serial protocol_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("merge", ["sum", "avg"])
@pytest.mark.parametrize("fanout", [2, 3])
def test_tree_matches_flat_protocol_step(merge, fanout):
    cfg = dataclasses.replace(K8, merge=merge)
    params, feats, y, loss_fn = _setup(cfg)
    loss_s, tg_s, sg_s, _ = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
    )
    tree = AggTree(num_clients=8, fanout=fanout)
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(8)]
    tr = SimTransport(workers)
    try:
        ex = Executor(tr, towers.mlp_tower_apply, loss_fn, merge,
                      microbatches=2, agg_tree=tree)
        res = ex.run_step(params["server"], y, features=feats)
    finally:
        ex.transport.close()
    np.testing.assert_allclose(res.loss, loss_s, atol=TREE_VERIFY_ATOL,
                               rtol=1e-5)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s))


def test_tree_pipeline_w2_matches_star_w2():
    """At window 2 the tree must reproduce the star's delayed-gradient
    trajectory (same schedule semantics, reassociated merge only)."""
    cfg = K8
    S, W, lr = 4, 2, 0.1
    params, feats, y, loss_fn = _setup(cfg)

    def run(tree):
        from repro.transport.builders import _sgd
        workers = [TowerWorker(k, towers.mlp_tower_apply,
                               params["towers"][k], optimizer=_sgd(lr))
                   for k in range(8)]
        tr = SimTransport(workers)
        sigma = params["server"]
        losses = []
        ex = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                      microbatches=2, agg_tree=tree)
        try:
            pipeline = StepPipeline(ex, window=W)

            def consume(res):
                nonlocal sigma
                sigma = jax.tree_util.tree_map(
                    lambda p, g: p - lr * g, sigma, res.server_grads)
                losses.append(float(res.loss))

            for s in range(S):
                res = pipeline.push(sigma, y, step=s, features=feats,
                                    collect_grads=False)
                if res is not None:
                    consume(res)
            for res in pipeline.flush(sigma, collect_grads=False):
                consume(res)
        finally:
            ex.transport.close()
        return losses

    star = run(None)
    treed = run(AggTree(num_clients=8, fanout=2))
    np.testing.assert_allclose(treed, star, atol=TREE_VERIFY_ATOL, rtol=1e-5)


def test_tree_composes_with_secure_aggregation():
    """The Secure Forward Aggregation property: partial sums of MASKED cuts
    stay blinded at relays and the pairwise masks cancel in role 0's
    full-tree sum — tree+secure must match the unmasked flat reference to
    the mask-cancellation tolerance."""
    cfg = dataclasses.replace(K8, merge="avg")
    params, feats, y, loss_fn = _setup(cfg)
    loss_s, tg_s, sg_s, _ = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, cfg.merge,
    )
    tree = AggTree(num_clients=8, fanout=2)
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(8)]
    tr = SimTransport(workers)
    try:
        ex = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                      microbatches=2, secure_agg=True,
                      agg_tree=tree)
        res = ex.run_step(params["server"], y, features=feats)
    finally:
        ex.transport.close()
    np.testing.assert_allclose(res.loss, loss_s, atol=1e-3, rtol=1e-3)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s),
                        atol=1e-3)
    # uplinks ride the tree tags with the masked payloads inside
    assert res.ledger.bytes_with_tag("tree_cut[0]") > 0
    assert res.ledger.bytes_with_tag("masked_cut[0]") == 0


# ---------------------------------------------------------------------------
# relay-worker semantics (direct handle() calls — no transport)
# ---------------------------------------------------------------------------

def test_relay_accumulates_out_of_order_across_adjacent_steps():
    cfg = dataclasses.replace(K8, num_clients=3,
                              client_feature_sizes=(6, 5, 5))
    params, feats, _, _ = _setup(cfg)
    w = TowerWorker(0, towers.mlp_tower_apply, params["towers"][0])
    assert w.handle({"op": "configure_relay", "children": [1, 2]}) == \
        {"op": "relay_ready", "client": 0}

    own = towers.mlp_tower_apply(params["towers"][0], feats[0])
    f = [jax.random.normal(jax.random.PRNGKey(10 + i), own.shape)
         for i in range(4)]
    # parts interleave across two in-flight steps, children before own cut
    assert w.handle({"op": "aggregate", "step": 1, "mb": 0, "child": 2,
                     "frame": f[0]}) is None
    assert w.handle({"op": "aggregate", "step": 0, "mb": 0, "child": 1,
                     "frame": f[1]}) is None
    assert w.handle({"op": "forward", "step": 1, "mb": 0,
                     "feats": feats[0]}) is None
    done1 = w.handle({"op": "aggregate", "step": 1, "mb": 0, "child": 1,
                      "frame": f[2]})
    assert done1 is not None and done1["op"] == "tree_cut"
    assert done1["step"] == 1 and done1["mb"] == 0
    # fixed deterministic order: own cut first, then children by id —
    # bit-identical to the hand-rolled accumulation in that order
    np.testing.assert_array_equal(done1["cut"], (own + f[2]) + f[0])
    # step 0 completes independently
    assert w.handle({"op": "forward", "step": 0, "mb": 0,
                     "feats": feats[0]}) is None
    done0 = w.handle({"op": "aggregate", "step": 0, "mb": 0, "child": 2,
                      "frame": f[3]})
    np.testing.assert_array_equal(done0["cut"], (own + f[1]) + f[3])
    # a duplicate part is a protocol violation, not a silent double-count
    w.handle({"op": "aggregate", "step": 2, "mb": 0, "child": 1,
              "frame": f[0]})
    with pytest.raises(ValueError, match="duplicate"):
        w.handle({"op": "aggregate", "step": 2, "mb": 0, "child": 1,
                  "frame": f[0]})


def test_relay_refuses_compression():
    w = TowerWorker(0, towers.mlp_tower_apply, None, compress="topk")
    with pytest.raises(ValueError, match="cannot compose"):
        w.handle({"op": "configure_relay", "children": [1]})


# ---------------------------------------------------------------------------
# response-pump routing over a real threaded transport
# ---------------------------------------------------------------------------

def test_tree_inproc_w2_with_lagging_child_matches_star():
    """Cross-step routing under load: a slow LEAF delays its relay's
    combined frames, so child parts for step t+1 interleave with step t's
    collection on the router thread — the trajectory must still match the
    star's (the relay accumulator is arrival-order-agnostic)."""
    cfg = dataclasses.replace(
        K8, num_clients=4, client_feature_sizes=(4,) * 4)
    params, feats, y, loss_fn = _setup(cfg)
    S, W = 3, 2

    def run(tree, delay):
        workers = [TowerWorker(k, towers.mlp_tower_apply,
                               params["towers"][k],
                               forward_delay_s=delay if k == 3 else 0.0)
                   for k in range(4)]
        ex = None
        losses = []
        tr = InprocTransport(workers)
        try:
            ex = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                          microbatches=2, agg_tree=tree)
            pipeline = StepPipeline(ex, window=W)
            for s in range(S):
                res = pipeline.push(params["server"], y, step=s,
                                    features=feats, collect_grads=False)
                if res is not None:
                    losses.append(float(res.loss))
            losses += [float(r.loss) for r in
                       pipeline.flush(params["server"], collect_grads=False)]
        finally:
            (ex.transport if ex is not None else tr).close()
        return losses

    star = run(None, 0.0)
    treed = run(AggTree(num_clients=4, fanout=2), 0.05)
    np.testing.assert_allclose(treed, star, atol=TREE_VERIFY_ATOL, rtol=1e-5)


def test_multiproc_tree_matches_and_wedged_relay_close_is_bounded():
    """Real spawned processes: the tree trains across the TCP loopback, and
    a relay wedged in a long forward cannot make ``close()`` hang — the
    router stops its pump, then the base transport escalates its shutdown
    (join -> terminate -> kill) and no child survives."""
    import time as _time

    cfg = dataclasses.replace(
        K8, num_clients=3, client_feature_sizes=(6, 5, 5))
    batch, M = 8, 1
    # driver-side reference regenerates the children's seeded state: the
    # workers rebuild params from param_seed=0 and serve their own feature
    # columns of the data_seed=0 step-0 stream (nothing crosses the wire)
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(
        jax.random.split(jax.random.PRNGKey(0), 2)[0],
        (batch, cfg.input_dim))
    y = jax.random.randint(jax.random.PRNGKey(7), (batch,), 0,
                           cfg.num_classes)
    feats = [x[:, jnp.asarray(s.indices)]
             for s in split_model.feature_slices(cfg)]

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, cfg.num_classes)

    loss_s, tg_s, sg_s, _ = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, cfg.merge,
    )
    tree = AggTree(num_clients=3, fanout=2)  # relay 0 <- child 2

    specs = [
        WorkerSpec(build_mlp_worker,
                   dict(cfg=cfg, param_seed=0, data_seed=0, batch=batch,
                        microbatches=M,
                        # wedge the RELAY's second-step forward far past the
                        # join timeout; step 0 is unaffected
                        forward_delay_s=30.0 if k == 0 else 0.0))
        for k in range(3)
    ]
    base = MultiprocTransport(specs)
    ex = Executor(base, towers.mlp_tower_apply, loss_fn, cfg.merge,
                  microbatches=M, agg_tree=tree)
    router = ex.transport
    assert isinstance(router, TreeRouter)
    try:
        res = ex.run_step(params["server"], y, step=0)
        np.testing.assert_allclose(res.loss, loss_s, atol=TREE_VERIFY_ATOL,
                                   rtol=1e-5)
        _assert_trees_close((res.tower_grads, res.server_grads),
                            (tg_s, sg_s))
        # wedge the relay: its step-1 forward sleeps 30s inside handle(),
        # so the shutdown request queues behind it unread
        ex.submit_step(1, y)
        _time.sleep(0.5)
    finally:
        t0 = _time.time()
        router.close()
        elapsed = _time.time() - t0
    # bounded: pump join (<= 5s) + shutdown join (10s) + terminate join —
    # never the 30s the wedged handler would take
    assert elapsed < 25.0, elapsed
    assert not any(p.is_alive() for p in base._procs)
    router.close()  # idempotent


# ---------------------------------------------------------------------------
# loud rejection of unsound combinations
# ---------------------------------------------------------------------------

def test_executor_rejects_unsound_tree_combinations():
    tree = AggTree(num_clients=2, fanout=2)
    workers = [TowerWorker(k, towers.mlp_tower_apply, None)
               for k in range(2)]
    tr = SimTransport(workers)
    with pytest.raises(ValueError, match="additively homomorphic"):
        Executor(tr, None, None, "max", agg_tree=tree)
    with pytest.raises(ValueError, match="merge_fn"):
        Executor(tr, None, None, "sum", agg_tree=tree,
                 merge_fn=lambda cuts, m: cuts[0])
    with pytest.raises(ValueError, match="compression"):
        Executor(tr, None, None, "sum", agg_tree=tree, compress="int8")
    with pytest.raises(ValueError, match="barrier"):
        Executor(tr, None, None, "avg", mode="nowait", agg_tree=tree)
    with pytest.raises(ValueError, match="tree covers"):
        Executor(tr, None, None, "sum",
                 agg_tree=AggTree(num_clients=3, fanout=2))
    ex = Executor(tr, None, None, "avg", agg_tree=tree)
    with pytest.raises(ValueError, match="barrier"):
        ex.collect_step(None, merge_mask=jnp.ones((2,)))
    ex.transport.close()


def test_tree_collect_rejects_liveness_and_merge_mask():
    cfg = dataclasses.replace(K8, num_clients=3,
                              client_feature_sizes=(6, 5, 5))
    params, feats, y, loss_fn = _setup(cfg, batch=8)
    tree = AggTree(num_clients=3, fanout=2)
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(3)]
    tr = SimTransport(workers)
    ex = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                  microbatches=1, agg_tree=tree)
    try:
        ex.submit_step(0, y, features=feats)
        with pytest.raises(ValueError, match="barrier-only"):
            ex.collect_step(params["server"], liveness=[[1, 1, 1]])
    finally:
        ex.transport.close()


def test_train_split_rejects_unsound_tree_runs():
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.train.loop import train_split

    cfg = get_arch("smollm-360m").reduced()
    loader = LMBatchLoader(cfg, 2, 16, seed=0)
    with pytest.raises(ValueError, match="no-wait"):
        train_split(cfg, loader, steps=1, batch=2, seq=16,
                    transport="inproc", runtime="nowait", agg_tree_fanout=2)
    comp = cfg.with_vertical(dataclasses.replace(
        cfg.vertical, compression="topk"))
    with pytest.raises(ValueError, match="compression"):
        train_split(comp, loader, steps=1, batch=2, seq=16,
                    transport="inproc", agg_tree_fanout=2)
    vlm = get_arch("internvl2-26b").reduced()
    with pytest.raises(ValueError, match="additive merge"):
        train_split(vlm, LMBatchLoader(vlm, 2, 16, seed=0), steps=1,
                    batch=2, seq=16, transport="inproc", agg_tree_fanout=2)


# ---------------------------------------------------------------------------
# train_split end-to-end: in-run step-0 tree verification at W=1 and W=2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime,mb,window", [("serial", 1, 1),
                                               ("pipelined", 2, 2)])
def test_train_split_tree_verifies_step0(runtime, mb, window):
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.train.loop import train_split

    cfg = get_arch("smollm-360m").reduced()
    loader = LMBatchLoader(cfg, 2, 16, seed=0)
    lines = []
    _, metrics, report = train_split(
        cfg, loader, steps=2, batch=2, seq=16, transport="inproc",
        runtime=runtime, microbatches=mb, inflight_steps=window,
        agg_tree_fanout=2, print_fn=lines.append)
    assert len(metrics.losses) == 2
    assert all(np.isfinite(v) for v in metrics.losses)
    assert any("aggregation tree: fanout 2" in ln for ln in lines)
    assert any("tree-merge verification" in ln and "OK" in ln
               for ln in lines)


# ---------------------------------------------------------------------------
# the engine's tree clock
# ---------------------------------------------------------------------------

def _plan(K, *, fanout=None, cut_bytes=4_000_000):
    return StepPlan(
        num_clients=K, microbatches=2,
        tower_fwd_flops=(1e7,) * K, tower_bwd_flops=(1e6,) * K,
        server_flops=1e7, cut_bytes=cut_bytes, head_bytes=1024,
        merge="sum", cut_elements=cut_bytes // 4, tree_fanout=fanout,
    )


def test_plan_rejects_unsound_tree():
    cfg = dataclasses.replace(K8, merge="max")
    with pytest.raises(ValueError, match="additively homomorphic"):
        plan_step(cfg, batch_size=16, tree_fanout=2)
    with pytest.raises(ValueError, match="compression"):
        plan_step(K8, batch_size=16, tree_fanout=2, compress="topk")
    with pytest.raises(ValueError, match=">= 2"):
        plan_step(K8, batch_size=16, tree_fanout=1)
    assert plan_step(K8, batch_size=16, tree_fanout=2).tree_fanout == 2


def test_serial_clock_shows_no_tree_win():
    """One strictly serial wall clock: the tree only moves merge work to
    relays (and adds hops), so the serial schedule cannot get faster."""
    link = LinkModel.uniform(16)
    star = simulate_serial(_plan(16), link).step_time_s
    tree = simulate_serial(_plan(16, fanout=2), link).step_time_s
    assert tree >= star


def test_pipelined_clock_shows_role0_nic_crossover():
    """With a finite role-0 NIC the star serializes K frames per microbatch
    through one resource; the fanout-2 tree serializes min(F, K).  The
    pipelined clock must show the tree winning at K=16 and the win growing
    with K — the simulator half of the benchmark's crossover claim."""
    def step_s(K, fanout):
        link = LinkModel.uniform(K, server_bandwidth_bps=1e8)
        return simulate_pipelined(_plan(K, fanout=fanout), link,
                                  steps=4, cross_step=2).step_time_s

    speedups = {K: step_s(K, None) / step_s(K, 2) for K in (4, 8, 16)}
    assert speedups[16] > 1.0, speedups
    assert speedups[16] > speedups[4], speedups
    # with the default infinite NIC the tree has nothing to win: the cut
    # chains up the depth-3 tree (leaf uplink -> relay downlink -> relay
    # add -> relay uplink -> ...) and the jacobian chains back down, so it
    # pays roughly one extra up+down transfer pair per level where the star
    # pays one hop — strictly slower, bounded by the depth, never a cliff
    link = LinkModel.uniform(8)
    star = simulate_pipelined(_plan(8), link, steps=4,
                              cross_step=2).step_time_s
    tree = simulate_pipelined(_plan(8, fanout=2), link, steps=4,
                              cross_step=2).step_time_s
    depth = AggTree(8, 2).depth
    assert star < tree < star * 2.0 * depth, (star, tree)

    with pytest.raises(ValueError, match="no-wait"):
        simulate_pipelined(_plan(8, fanout=2), link, mode="nowait")
