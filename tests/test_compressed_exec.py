"""Compressed cut traffic on the execution hot path, end-to-end:

* transport-parametrized compressed-vs-serial-reference equivalence
  (sim/inproc/multiproc, paper MLP + dense/moe SplitPrograms) — the wire
  path must reproduce the serial ``protocol_step`` running the SAME codec;
* compressed-vs-PLAIN gradient deviation bounded by the documented
  ``compression.GRAD_VS_PLAIN_ATOL`` (the accuracy cost of the lossy wire);
* ledger-vs-``costs.wire_bytes`` byte reconciliation for the compressed
  cut uplinks and jacobian downlinks, exact per step — including on
  magnitude-tied inputs (the topk tie-bug regression: ties kept > k
  entries, which now shows up as a byte mismatch instead of passing);
* error-feedback residual correctness: the same per-stream carry at
  driver window W=1 and W=2, and the step-1 payload equals
  ``C(cut + residual_0)`` by construction;
* loud failure on unsupported combinations (secure_agg, merge_fn
  programs, unknown schemes) at the Executor, train_split, and launcher;
* the engine prices compressed links in ``StepPlan`` for both simulators.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vertical_mlp import MLPSplitConfig
from repro.core import compression as comp
from repro.core import costs, protocol, split_model, towers
from repro.runtime.executor import Executor
from repro.transport import (InprocTransport, MultiprocTransport,
                             SimTransport, TowerWorker, WorkerSpec,
                             build_mlp_worker)

TINY = MLPSplitConfig(
    name="comp_tiny", input_dim=16, num_classes=2, num_clients=3,
    client_feature_sizes=(6, 5, 5), tower_hidden=(16,), cut_dim=8,
    server_hidden=(16,), merge="avg",
)

FRACTION = 0.25


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


def _assert_trees_close(a, b, atol=1e-4):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=1e-3)


def _max_tree_dev(a, b):
    return max(float(jnp.max(jnp.abs(la - lb)))
               for la, lb in zip(jax.tree_util.tree_leaves(a),
                                 jax.tree_util.tree_leaves(b)))


class RecordingSimTransport(SimTransport):
    """SimTransport that snapshots what role 0 observes on the uplink —
    the audit surface for the wire-payload assertions."""

    def __init__(self, workers):
        super().__init__(workers)
        self.observed_cuts: dict = {}  # (step, mb, client) -> array

    def next_response(self, timeout=None):
        got = super().next_response(timeout)
        if got is not None:
            k, resp = got
            if resp["op"] == "cut":
                self.observed_cuts[(resp["step"], resp["mb"], k)] = \
                    np.asarray(resp["cut"])
        return got


def _audit_ledger(ledger, cfg, batch, M, scheme):
    """Ledger-vs-costs reconciliation: every cut/jac byte rides the
    compressed tags at EXACTLY the codec's analytic wire bytes, and the
    plain tags are empty."""
    K = cfg.num_clients
    want = M * costs.wire_bytes((batch // M, cfg.cut_dim), 4, scheme,
                                FRACTION)
    for k in range(K):
        assert ledger.bytes_with_tag(f"compressed_cut[{k}]") == want
        assert ledger.bytes_with_tag(f"compressed_jac[{k}]") == want
        assert ledger.bytes_with_tag(f"cut[{k}]") == 0
        assert ledger.bytes_with_tag(f"jac[{k}]") == 0


# ---------------------------------------------------------------------------
# compressed transport matches the serial reference running the same codec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport_cls", [SimTransport, InprocTransport])
@pytest.mark.parametrize("scheme", comp.SCHEMES)
def test_compressed_matches_serial_reference_mlp(transport_cls, scheme):
    """Pipelined M=2 execution over a real transport reproduces the serial
    ``protocol_step`` running the same compression (both start from zero
    error-feedback residual), and the ledger audits codec bytes exactly."""
    cfg, batch, M = TINY, 16, 2
    params, feats, y, loss_fn = _setup(cfg, batch=batch)
    loss_s, tg_s, sg_s, _ = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, cfg.merge,
        compress=scheme, topk_fraction=FRACTION,
    )

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                           compress=scheme, topk_fraction=FRACTION)
               for k in range(cfg.num_clients)]
    tr = transport_cls(workers)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=M,
                            compress=scheme, topk_fraction=FRACTION)
        res = executor.run_step(params["server"], y, features=feats)
    finally:
        tr.close()

    np.testing.assert_allclose(res.loss, loss_s, atol=1e-4, rtol=1e-3)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s))
    _audit_ledger(res.ledger, cfg, batch, M, scheme)


@pytest.mark.parametrize("transport_cls", [SimTransport, InprocTransport])
@pytest.mark.parametrize("scheme", comp.SCHEMES)
@pytest.mark.parametrize("family,arch", [("dense", "smollm-360m"),
                                         ("moe", "deepseek-moe-16b")])
def test_compressed_family_matches_serial_and_bounds_plain_dev(
        family, arch, scheme, transport_cls):
    """Per-SplitProgram-family acceptance: the compressed wire path matches
    the compressed serial reference tightly, and deviates from the PLAIN
    gradients by no more than the documented per-scheme tolerance."""
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.models import backbone, split_program

    base = get_arch(arch).reduced()
    assert base.family == family
    cfg = base.with_vertical(dataclasses.replace(
        base.vertical, compression=scheme, topk_fraction=FRACTION))
    program = split_program.get_program(cfg)
    params = backbone.init_params(cfg, jax.random.PRNGKey(0))
    towers_p, server_p = program.partition(params)
    b = {k: jnp.asarray(v) for k, v in
         LMBatchLoader(cfg, 2, 16, seed=0).next_batch().items()}
    feats, ctx = program.features(b), program.batch_ctx(b)

    # compressed serial reference (program.protocol_step reads cfg)
    loss_c, tg_c, sg_c, _ = program.protocol_step(
        towers_p, server_p, feats, ctx)
    # plain serial reference on the uncompressed config
    plain = split_program.get_program(base)
    loss_p, tg_p, sg_p, _ = plain.protocol_step(
        towers_p, server_p, feats, ctx)

    workers = [TowerWorker(k, program.tower_fwd(k), towers_p[k],
                           compress=scheme, topk_fraction=FRACTION)
               for k in range(program.num_clients)]
    tr = transport_cls(workers)
    try:
        executor = Executor(tr, program.server_fwd, program.loss_fn,
                            program.merge, microbatches=1,
                            compress=scheme, topk_fraction=FRACTION,
                            **program.executor_kwargs)
        res = executor.run_step(server_p, ctx, features=feats)
    finally:
        tr.close()

    # wire path == compressed serial reference (same codec, zero residual)
    np.testing.assert_allclose(res.loss, loss_c, atol=1e-3, rtol=1e-3)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_c, sg_c),
                        atol=comp.STEP0_VERIFY_ATOL)
    # lossy-wire accuracy cost vs the plain gradients, documented bound
    atol = comp.GRAD_VS_PLAIN_ATOL[scheme]
    dev = _max_tree_dev((res.tower_grads, res.server_grads), (tg_p, sg_p))
    assert dev <= atol, (
        f"{family}/{scheme}: compressed grads deviate {dev:.3f} from plain, "
        f"documented bound {atol}")
    assert abs(float(res.loss) - float(loss_p)) <= atol
    assert res.ledger.bytes_with_tag("compressed_cut[0]") > 0
    if program.has_aux:
        assert res.aux is not None and float(res.aux) > 0


# ---------------------------------------------------------------------------
# multiproc: real spawned processes + TCP loopback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", comp.SCHEMES)
def test_multiproc_compressed_loopback_matches_and_audits(scheme):
    """The acceptance path over real OS processes: compressed uplinks and
    downlinks cross TCP, gradients match the compressed serial reference,
    the ledger reconciles against ``costs.wire_bytes`` — and ``close()``
    leaves no surviving children."""
    cfg = dataclasses.replace(TINY, num_clients=2,
                              client_feature_sizes=(8, 8))
    batch, M = 16, 2
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
        compress=scheme, topk_fraction=FRACTION,
    )

    specs = [
        WorkerSpec(build_mlp_worker,
                   dict(cfg=cfg, param_seed=0, data_seed=0, batch=batch,
                        microbatches=M, compress=scheme,
                        topk_fraction=FRACTION))
        for _ in range(cfg.num_clients)
    ]
    tr = MultiprocTransport(specs)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=M,
                            compress=scheme, topk_fraction=FRACTION)
        res = executor.run_step(params["server"], y, step=0)
    finally:
        tr.close()

    np.testing.assert_allclose(res.loss, loss_s, atol=1e-3, rtol=1e-3)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s),
                        atol=1e-3)
    _audit_ledger(res.ledger, cfg, batch, M, scheme)
    # the terminate->kill escalation ran: no child outlives the transport
    assert not any(p.is_alive() for p in tr._procs)


# ---------------------------------------------------------------------------
# error feedback: the per-stream residual carry, W=1 vs W=2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", comp.SCHEMES)
def test_error_feedback_residual_carries_across_steps(scheme):
    """With frozen params and identical features every step, the observed
    uplinks follow the EF recursion exactly: step 0 ships ``C(cut)``,
    step 1 ships ``C(cut + r0)`` with ``r0 = cut - C(cut)`` — so the wire
    traffic is NOT a constant replay of the first lossy encode."""
    cfg = TINY
    params, feats, y, loss_fn = _setup(cfg, batch=8)
    raw = [towers.mlp_tower_apply(params["towers"][k], feats[k])
           for k in range(cfg.num_clients)]

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                           compress=scheme, topk_fraction=FRACTION)
               for k in range(cfg.num_clients)]
    tr = RecordingSimTransport(workers)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=1,
                            compress=scheme, topk_fraction=FRACTION)
        for step in range(2):
            executor.run_step(params["server"], y, step=step, features=feats,
                              collect_grads=False)
    finally:
        tr.close()

    for k in range(cfg.num_clients):
        c0 = comp.apply_compression(raw[k], scheme, FRACTION)
        r0 = raw[k] - c0
        c1 = comp.apply_compression(raw[k] + r0, scheme, FRACTION)
        np.testing.assert_allclose(tr.observed_cuts[(0, 0, k)], c0,
                                   atol=1e-6)
        np.testing.assert_allclose(tr.observed_cuts[(1, 0, k)], c1,
                                   atol=1e-6)
        # the residual actually changed the payload (lossy encode != exact)
        assert float(jnp.max(jnp.abs(c1 - c0))) > 0


@pytest.mark.parametrize("scheme", comp.SCHEMES)
def test_error_feedback_identical_at_window_1_and_2(scheme):
    """Driver window must not perturb the per-stream residual carry: steps
    are collected oldest-first, so W=2 cross-step pipelining ships exactly
    the byte-identical uplink sequence W=1 does (frozen params)."""
    from repro.runtime.pipeline import StepPipeline

    cfg = TINY
    params, feats, y, loss_fn = _setup(cfg, batch=8)
    steps = 4

    def run(window):
        workers = [TowerWorker(k, towers.mlp_tower_apply,
                               params["towers"][k], compress=scheme,
                               topk_fraction=FRACTION)
                   for k in range(cfg.num_clients)]
        tr = RecordingSimTransport(workers)
        losses = []
        try:
            executor = Executor(tr, towers.mlp_tower_apply, loss_fn,
                                cfg.merge, microbatches=1,
                                compress=scheme, topk_fraction=FRACTION)
            pipe = StepPipeline(executor, window=window)
            for step in range(steps):
                res = pipe.push(params["server"], y, step=step,
                                features=feats, collect_grads=False)
                if res is not None:
                    losses.append(float(res.loss))
            losses.extend(float(r.loss)
                          for r in pipe.flush(params["server"],
                                              collect_grads=False))
        finally:
            tr.close()
        return losses, dict(tr.observed_cuts)

    losses1, cuts1 = run(1)
    losses2, cuts2 = run(2)
    np.testing.assert_allclose(losses1, losses2, rtol=1e-6)
    assert cuts1.keys() == cuts2.keys()
    for key in cuts1:
        np.testing.assert_array_equal(cuts1[key], cuts2[key])
    # the carry is live: consecutive steps ship different payloads
    moved = any(
        float(np.max(np.abs(cuts1[(s + 1, 0, k)] - cuts1[(s, 0, k)]))) > 0
        for s in range(steps - 1) for k in range(cfg.num_clients))
    assert moved


# ---------------------------------------------------------------------------
# topk tie regression: the ledger-vs-costs audit on tied magnitudes
# ---------------------------------------------------------------------------

def test_tied_magnitudes_keep_exactly_k_and_reconcile_bytes():
    """All-equal cut magnitudes are the tie-bug's worst case: a >= cutoff
    selection keeps every entry, blowing the k-per-vector wire contract.
    The payload must hold exactly k nonzeros per vector and the ledger must
    equal the analytic ``costs.wire_bytes`` — the audit that turns the tie
    bug into a loud byte mismatch."""
    cfg, batch, M = TINY, 8, 2
    params, feats, y, loss_fn = _setup(cfg, batch=batch)

    def tied_tower(tp, x):  # every activation magnitude identical
        return jnp.ones((x.shape[0], cfg.cut_dim))

    workers = [TowerWorker(k, tied_tower, params["towers"][k],
                           compress="topk", topk_fraction=FRACTION)
               for k in range(cfg.num_clients)]
    tr = RecordingSimTransport(workers)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=M,
                            compress="topk", topk_fraction=FRACTION)
        res = executor.run_step(params["server"], y, features=feats,
                                collect_grads=False)
    finally:
        tr.close()

    k_keep = comp.topk_count(cfg.cut_dim, FRACTION)
    for (step, mb, client), cut in tr.observed_cuts.items():
        nnz_per_row = (cut != 0).sum(axis=-1)
        assert (nnz_per_row == k_keep).all(), (
            f"client {client} mb {mb}: tie kept {nnz_per_row.max()} > "
            f"{k_keep} entries per vector")
    want = M * costs.wire_bytes((batch // M, cfg.cut_dim), 4, "topk",
                                FRACTION)
    for c in range(cfg.num_clients):
        assert res.ledger.bytes_with_tag(f"compressed_cut[{c}]") == want


# ---------------------------------------------------------------------------
# loud failure on unsupported combinations
# ---------------------------------------------------------------------------

def test_unsupported_combinations_raise_at_construction():
    tr = SimTransport([])
    with pytest.raises(ValueError, match="secure aggregation"):
        Executor(tr, None, None, "avg", secure_agg=True, compress="topk")
    with pytest.raises(ValueError, match="merge_fn"):
        Executor(tr, None, None, "sum", compress="int8",
                 merge_fn=lambda cuts, m: cuts[0])
    with pytest.raises(ValueError, match="unknown compression scheme"):
        Executor(tr, None, None, "avg", compress="gzip")
    with pytest.raises(ValueError, match="cannot compose"):
        protocol.step_schedule(2, secure=True, compress="topk")
    with pytest.raises(ValueError, match="unknown compression scheme"):
        TowerWorker(0, towers.mlp_tower_apply, {}, compress="gzip")


def test_worker_refuses_key_exchange_under_compression():
    """The privacy principal's own guard: a compressing worker must not
    join a key exchange (its uplinks would not be maskable aggregates)."""
    worker = TowerWorker(0, towers.mlp_tower_apply, {}, compress="topk")
    with pytest.raises(ValueError, match="compress"):
        worker.handle({"op": "key_exchange", "num_clients": 2})


def test_train_split_rejects_compress_plus_secure():
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.train.loop import train_split

    cfg = get_arch("smollm-360m").reduced()
    cfg = cfg.with_vertical(dataclasses.replace(
        cfg.vertical, secure_aggregation=True, compression="topk"))
    with pytest.raises(ValueError, match="cannot compose"):
        train_split(cfg, LMBatchLoader(cfg, 2, 16, seed=0), steps=1,
                    batch=2, seq=16, transport="inproc")


def test_launcher_rejects_compress_plus_secure_agg():
    from repro.launch.train import main

    with pytest.raises(SystemExit, match="--compress cannot run with"):
        main(["--arch", "smollm-360m", "--reduced", "--steps", "1",
              "--transport", "inproc", "--compress", "topk",
              "--secure-agg"])
    with pytest.raises(SystemExit, match="topk-fraction"):
        main(["--arch", "smollm-360m", "--reduced", "--steps", "1",
              "--transport", "inproc", "--compress", "topk",
              "--topk-fraction", "1.5"])


# ---------------------------------------------------------------------------
# train_split end-to-end with in-run step-0 verification, W=1 and W=2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", comp.SCHEMES)
@pytest.mark.parametrize("runtime,inflight", [("serial", 1),
                                              ("pipelined", 2)])
def test_train_split_compressed_verifies_step0(scheme, runtime, inflight):
    """train_split under compression trains, and its step-0 compressed-wire
    verification passes against the serial reference at the documented
    tolerance — at W=1 and with cross-step pipelining W=2 (step 0's
    forwards run on initial params either way, so the zero-residual
    reference stays valid)."""
    import re

    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.train.loop import train_split

    cfg = get_arch("smollm-360m").reduced()
    cfg = cfg.with_vertical(dataclasses.replace(
        cfg.vertical, compression=scheme, topk_fraction=FRACTION))
    loader = LMBatchLoader(cfg, 2, 16, seed=0)
    lines = []
    params, metrics, report = train_split(
        cfg, loader, steps=2, batch=2, seq=16, transport="inproc",
        runtime=runtime, inflight_steps=inflight, print_fn=lines.append)
    assert len(metrics.losses) == 2
    assert all(np.isfinite(v) for v in metrics.losses)
    assert any("compressed-wire verification" in ln and "OK" in ln
               for ln in lines)
    ratio_lines = [ln for ln in lines if "compressed cut uplink" in ln]
    assert ratio_lines
    ratio = float(re.search(r"\(([\d.]+)x\)", ratio_lines[0]).group(1))
    if scheme == "topk":
        assert ratio <= 0.35  # the acceptance bound for fraction 0.25
    else:
        assert ratio < 1.0


# ---------------------------------------------------------------------------
# the engine prices compressed links in both simulators
# ---------------------------------------------------------------------------

def test_engine_prices_compressed_links():
    from repro.runtime import LinkModel, simulate_pipelined, simulate_serial
    from repro.runtime.engine import plan_step

    cfg = TINY
    link = LinkModel.uniform(cfg.num_clients)
    plain = plan_step(cfg, batch_size=32, microbatches=2)
    topk = plan_step(cfg, batch_size=32, microbatches=2, compress="topk",
                     topk_fraction=FRACTION)
    q8 = plan_step(cfg, batch_size=32, microbatches=2, compress="int8")
    assert topk.cut_bytes == costs.wire_bytes((16, cfg.cut_dim), 4, "topk",
                                              FRACTION)
    assert q8.cut_bytes == costs.wire_bytes((16, cfg.cut_dim), 4, "int8")
    assert topk.cut_bytes < plain.cut_bytes
    assert q8.cut_bytes < plain.cut_bytes
    # both simulators clock the smaller payload in BOTH cut directions
    for sim in (lambda p: simulate_serial(p, link, steps=2).total_time_s,
                lambda p: simulate_pipelined(p, link, steps=2,
                                             cross_step=2).total_time_s):
        assert sim(topk) < sim(plain)
        assert sim(q8) < sim(plain)
    with pytest.raises(ValueError, match="cannot compose"):
        plan_step(cfg, batch_size=32, secure=True, compress="topk")


def test_plan_from_arch_reads_compression_config():
    from repro.configs.base import get_arch
    from repro.runtime.engine import plan_from_arch

    cfg = get_arch("smollm-360m").reduced()
    plain = plan_from_arch(cfg, 4, 16)
    assert plain.compress is None
    comp_cfg = cfg.with_vertical(dataclasses.replace(
        cfg.vertical, compression="topk", topk_fraction=FRACTION))
    p = plan_from_arch(comp_cfg, 4, 16)
    assert p.compress == "topk" and p.cut_bytes < plain.cut_bytes
    # the explicit override beats the config, like `secure`
    p8 = plan_from_arch(cfg, 4, 16, compress="int8")
    assert p8.compress == "int8" and p8.cut_bytes < plain.cut_bytes
    with pytest.raises(ValueError, match="cannot compose"):
        plan_from_arch(comp_cfg, 4, 16, secure=True)
