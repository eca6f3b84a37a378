"""Secure aggregation as a protocol phase, end-to-end over real transports:

* transport-parametrized secure-vs-plain equivalence (sim/inproc/multiproc,
  dense + moe SplitPrograms and the paper MLP) — the masked merge must
  reproduce the unmasked gradients to the mask-cancellation tolerance;
* ledger-vs-``costs`` byte reconciliation for the one-time key-exchange
  round and the masked cut uplinks;
* privacy audits: role 0's per-client observations are provably masked
  (distance-correlation leakage drop vs raw uplinks) and fresh per round
  (consecutive steps/microbatches cannot be differenced to raw deltas);
* loud failure on unsupported combinations (nowait, merge_fn programs,
  non-additive merges) instead of a silent unmasked run;
* the engine clocks the key exchange as a one-time setup round.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vertical_mlp import MLPSplitConfig
from repro.core import costs, protocol, split_model, towers
from repro.core.leakage import distance_correlation
from repro.core.secure_agg import KEYX_GROUP_BYTES
from repro.runtime.executor import Executor
from repro.transport import (InprocTransport, MultiprocTransport,
                             SimTransport, TowerWorker, WorkerSpec,
                             build_mlp_worker)

TINY = MLPSplitConfig(
    name="secure_tiny", input_dim=16, num_classes=2, num_clients=3,
    client_feature_sizes=(6, 5, 5), tower_hidden=(16,), cut_dim=8,
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


def _assert_trees_close(a, b, atol=1e-4):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=1e-3)


class RecordingSimTransport(SimTransport):
    """SimTransport that snapshots what role 0 OBSERVES on the uplink —
    the audit surface for the privacy assertions."""

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


# ---------------------------------------------------------------------------
# secure-vs-plain equivalence: MLP over sim/inproc
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transport_cls", [SimTransport, InprocTransport])
@pytest.mark.parametrize("merge", ["avg", "sum"])
def test_secure_matches_plain_mlp(transport_cls, merge):
    cfg = dataclasses.replace(TINY, merge=merge)
    params, feats, y, loss_fn = _setup(cfg)
    loss_s, tg_s, sg_s, ledger_s = protocol.protocol_step(
        towers.mlp_tower_apply, towers.mlp_tower_apply, loss_fn,
        params["towers"], params["server"], feats, y, merge,
    )

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(cfg.num_clients)]
    tr = transport_cls(workers)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, merge,
                            microbatches=2,
                            secure_agg=True)
        res = executor.run_step(params["server"], y, features=feats)
    finally:
        tr.close()

    np.testing.assert_allclose(res.loss, loss_s, atol=1e-3, rtol=1e-3)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s))
    # uplinks re-tagged: every cut byte rides masked_cut[k], none ride cut[k]
    K = cfg.num_clients
    masked_bytes = sum(res.ledger.bytes_with_tag(f"masked_cut[{k}]")
                       for k in range(K))
    plain_bytes = sum(ledger_s.bytes_with_tag(f"cut[{k}]") for k in range(K))
    assert masked_bytes == plain_bytes  # f32 masks add zero byte overhead
    assert all(res.ledger.bytes_with_tag(f"cut[{k}]") == 0 for k in range(K))


# ---------------------------------------------------------------------------
# secure-vs-plain equivalence per SplitProgram family (dense + moe)
# ---------------------------------------------------------------------------

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


@pytest.mark.parametrize("transport_cls", [SimTransport, InprocTransport])
@pytest.mark.parametrize("family,arch", [("dense", "smollm-360m"),
                                         ("moe", "deepseek-moe-16b")])
def test_secure_family_matches_serial_protocol(family, arch, transport_cls):
    """Sum/avg-merge families train masked to the unmasked serial reference
    (the §3 identity survives the masking because the pairwise masks cancel
    in the merge) — and the moe aux loss still rides its slot."""
    cfg, program, towers_p, server_p, b = _family_setup(arch)
    assert cfg.family == family
    feats, ctx = program.features(b), program.batch_ctx(b)
    loss_s, tg_s, sg_s, _ = program.protocol_step(
        towers_p, server_p, feats, ctx)

    workers = [TowerWorker(k, program.tower_fwd(k), towers_p[k])
               for k in range(program.num_clients)]
    tr = transport_cls(workers)
    try:
        executor = Executor(tr, program.server_fwd, program.loss_fn,
                            program.merge, microbatches=1,
                            secure_agg=True, **program.executor_kwargs)
        res = executor.run_step(server_p, ctx, features=feats)
    finally:
        tr.close()
    np.testing.assert_allclose(res.loss, loss_s, atol=1e-3, rtol=1e-3)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s),
                        atol=1e-3)
    assert res.ledger.bytes_with_tag("masked_cut[0]") > 0
    if program.has_aux:
        assert res.aux is not None and float(res.aux) > 0


# ---------------------------------------------------------------------------
# multiproc: real spawned processes + TCP loopback, bytes reconciled
# ---------------------------------------------------------------------------

def test_multiproc_secure_loopback_matches_and_reconciles():
    """The acceptance path: the key exchange and masked uplinks cross a real
    process boundary; gradients match the unmasked serial reference and the
    keyx/masked bytes reconcile ledger-vs-``costs``."""
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
    )

    specs = [
        WorkerSpec(build_mlp_worker,
                   dict(cfg=cfg, param_seed=0, data_seed=0, batch=batch,
                        microbatches=M))
        for _ in range(cfg.num_clients)
    ]
    with MultiprocTransport(specs) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=M,
                            secure_agg=True)
        keyx = executor.setup_secure()
        res = executor.run_step(params["server"], y, step=0)

    np.testing.assert_allclose(res.loss, loss_s, atol=1e-3, rtol=1e-3)
    _assert_trees_close((res.tower_grads, res.server_grads), (tg_s, sg_s))

    # key-exchange bytes: ledger vs the analytic model, tag by tag
    K = cfg.num_clients
    want = costs.key_exchange_bytes(K)
    for k in range(K):
        assert (keyx.bytes_with_tag(f"keyx_pub[{k}]")
                == want["pub_bytes_per_client"] == KEYX_GROUP_BYTES)
        assert (keyx.bytes_with_tag(f"keyx_bcast[{k}]")
                == want["bcast_bytes_per_client"] == K * KEYX_GROUP_BYTES)
    assert keyx.received_by("role0") == want["role0_received"]
    assert keyx.sent_by("role0") == want["role0_sent"]
    assert keyx.total() == want["total"]

    # masked uplinks: per-client, per-microbatch f32 cut payloads
    mb = batch // M
    assert (res.ledger.bytes_with_tag("masked_cut[0]")
            == M * costs.masked_cut_bytes(mb, cfg.cut_dim))


# ---------------------------------------------------------------------------
# privacy audits at role 0's observation surface
# ---------------------------------------------------------------------------

def test_role0_observations_are_masked_and_leak_less():
    """Distance-correlation audit: what role 0 actually drains off the
    transport under secure aggregation must (a) differ from the raw cut by
    the mask scale and (b) carry far less raw-feature structure (dCor) than
    the unmasked uplink."""
    cfg = TINY
    params, feats, y, loss_fn = _setup(cfg, batch=64)
    raw_cuts = [towers.mlp_tower_apply(params["towers"][k], feats[k])
                for k in range(cfg.num_clients)]

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(cfg.num_clients)]
    tr = RecordingSimTransport(workers)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=1,
                            secure_agg=True, secure_scale=10.0)
        executor.run_step(params["server"], y, features=feats)
    finally:
        tr.close()

    for k in range(cfg.num_clients):
        observed = jnp.asarray(tr.observed_cuts[(0, 0, k)])
        # (a) blinded: nowhere near the raw activation
        dev = float(jnp.mean(jnp.abs(observed - raw_cuts[k])))
        assert dev > 1.0, f"client {k} uplink insufficiently masked ({dev})"
        # (b) less raw-feature structure than the unmasked uplink.  The
        # sample dCor is a biased V-statistic with a nonzero floor even for
        # INDEPENDENT arrays at this n, so the yardstick is that floor: the
        # masked uplink must sit at the independent-noise baseline, far
        # below the raw uplink's structure
        baseline = float(distance_correlation(
            feats[k],
            jax.random.normal(jax.random.PRNGKey(100 + k),
                              raw_cuts[k].shape)))
        dcor_raw = float(distance_correlation(feats[k], raw_cuts[k]))
        dcor_masked = float(distance_correlation(feats[k], observed))
        assert dcor_raw > baseline + 0.15, (
            f"client {k}: raw uplink carries no measurable structure "
            f"(dCor {dcor_raw:.3f} vs baseline {baseline:.3f}) — "
            "the audit has nothing to show")
        assert dcor_masked < dcor_raw - 0.15, (
            f"client {k}: masked dCor {dcor_masked:.3f} !<< raw "
            f"{dcor_raw:.3f}")
        assert dcor_masked < baseline + 0.1, (
            f"client {k}: masked dCor {dcor_masked:.3f} above the "
            f"independent-noise floor {baseline:.3f}")


def test_executor_rounds_are_fresh_per_step_and_microbatch():
    """Mask-reuse regression at the execution layer: with identical
    features, identical params (no local optimizer) and M=2 identical
    microbatches, every uplink role 0 observes across two steps must be
    pairwise distinct — differencing any two recovers mask noise, never the
    (zero) raw activation delta."""
    cfg = TINY
    params, feats, y, loss_fn = _setup(cfg, batch=16)
    # both microbatches see the same rows -> identical raw cuts everywhere
    feats = [jnp.concatenate([f[:8], f[:8]]) for f in feats]

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(cfg.num_clients)]
    tr = RecordingSimTransport(workers)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=2,
                            secure_agg=True)
        for step in range(2):
            executor.run_step(params["server"], y, step=step, features=feats,
                              collect_grads=False)
    finally:
        tr.close()

    for k in range(cfg.num_clients):
        views = [tr.observed_cuts[(s, m, k)] for s in (0, 1) for m in (0, 1)]
        for i in range(len(views)):
            for j in range(i + 1, len(views)):
                leak = float(np.mean(np.abs(views[i] - views[j])))
                assert leak > 0.5, (
                    f"client {k}: uplinks {i} and {j} difference to the raw "
                    f"delta (mean |diff| {leak:.2e}) — masks were reused")


def test_recycled_step_id_cannot_reuse_masks():
    """Mask freshness survives API misuse: looping ``run_step`` without a
    step id (so step=0 recycles after retirement) would derive the same
    round indices and let role 0 difference two uplinks to the raw
    activation delta — both the executor (early, friendly) and the worker
    (the privacy principal, transport-level) must refuse."""
    cfg = TINY
    params, feats, y, loss_fn = _setup(cfg, batch=8)

    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(cfg.num_clients)]
    tr = SimTransport(workers)
    try:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, cfg.merge,
                            microbatches=1,
                            secure_agg=True)
        executor.run_step(params["server"], y, features=feats,
                          collect_grads=False)  # default step=0
        with pytest.raises(ValueError, match="strictly increasing"):
            executor.run_step(params["server"], y, features=feats,
                              collect_grads=False)  # step=0 again
    finally:
        tr.close()

    # and independently at the worker, which must not trust the driver
    worker = workers[0]
    assert worker._secure is not None
    with pytest.raises(ValueError, match="round .* already used"):
        worker.handle({"op": "forward", "step": 0, "mb": 0,
                       "feats": feats[0]})


# ---------------------------------------------------------------------------
# loud failure on unsupported combinations
# ---------------------------------------------------------------------------

def test_unsupported_combinations_raise_at_construction():
    tr = SimTransport([])
    with pytest.raises(ValueError, match="additively homomorphic"):
        Executor(tr, None, None, "max", secure_agg=True)
    with pytest.raises(ValueError, match="merge_fn"):
        Executor(tr, None, None, "sum", secure_agg=True,
                 merge_fn=lambda cuts, m: cuts[0])
    with pytest.raises(ValueError, match="barrier"):
        Executor(tr, None, None, "avg", mode="nowait", secure_agg=True)
    ex = Executor(tr, None, None, "avg", secure_agg=True)
    with pytest.raises(ValueError, match="barrier"):
        ex.collect_step(None, merge_mask=jnp.ones((2,)))


def test_train_split_rejects_secure_on_unsupported_paths():
    """The dead-flag fix: secure_aggregation=True must never silently train
    unmasked — unsupported runtime/program combinations raise actionably
    (and before any worker is spawned)."""
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.train.loop import train_split

    cfg = get_arch("smollm-360m").reduced()
    cfg = cfg.with_vertical(dataclasses.replace(
        cfg.vertical, secure_aggregation=True))
    loader = LMBatchLoader(cfg, 2, 16, seed=0)
    with pytest.raises(ValueError, match="no-wait"):
        train_split(cfg, loader, steps=1, batch=2, seq=16,
                    transport="inproc", runtime="nowait")

    vlm = get_arch("internvl2-26b").reduced()
    vlm = vlm.with_vertical(dataclasses.replace(
        vlm.vertical, secure_aggregation=True))
    with pytest.raises(ValueError, match="merge_fn"):
        train_split(vlm, LMBatchLoader(vlm, 2, 16, seed=0), steps=1,
                    batch=2, seq=16, transport="inproc")


def test_train_split_secure_trains_with_step0_masked_verification():
    """The wired flag end-to-end: train_split under secure aggregation runs
    the key exchange, trains, and its step-0 masked-merge verification
    passes against the serial protocol_step."""
    from repro.configs.base import get_arch
    from repro.data.loader import LMBatchLoader
    from repro.train.loop import train_split

    cfg = get_arch("smollm-360m").reduced()
    cfg = cfg.with_vertical(dataclasses.replace(
        cfg.vertical, secure_aggregation=True))
    loader = LMBatchLoader(cfg, 2, 16, seed=0)
    lines = []
    params, metrics, report = train_split(
        cfg, loader, steps=2, batch=2, seq=16, transport="inproc",
        runtime="serial", print_fn=lines.append)
    assert len(metrics.losses) == 2
    assert all(np.isfinite(v) for v in metrics.losses)
    assert any("key exchange complete" in ln for ln in lines)
    assert any("masked-merge verification" in ln and "OK" in ln
               for ln in lines)


# ---------------------------------------------------------------------------
# the engine clocks the key exchange as a one-time setup round
# ---------------------------------------------------------------------------

def test_engine_clocks_key_exchange_once():
    from repro.runtime import LinkModel, simulate_pipelined, simulate_serial
    from repro.runtime.engine import plan_step

    cfg = dataclasses.replace(TINY, merge="avg")
    link = LinkModel.uniform(cfg.num_clients)
    plain = plan_step(cfg, batch_size=32, microbatches=2)
    secure = plan_step(cfg, batch_size=32, microbatches=2, secure=True)
    assert plain.keyx_bytes == 0 and secure.keyx_bytes == KEYX_GROUP_BYTES

    # serial: the setup round is paid once, not per step
    s1p, s1s = (simulate_serial(p, link, steps=1) for p in (plain, secure))
    s4p, s4s = (simulate_serial(p, link, steps=4) for p in (plain, secure))
    assert s1s.total_time_s > s1p.total_time_s
    np.testing.assert_allclose(s4s.total_time_s - s4p.total_time_s,
                               s1s.total_time_s - s1p.total_time_s,
                               rtol=1e-9)

    # pipelined (any window): same one-time property
    def total(p, steps):
        return simulate_pipelined(p, link, steps=steps,
                                  cross_step=2).total_time_s

    assert total(secure, 1) > total(plain, 1)
    np.testing.assert_allclose(total(secure, 4) - total(plain, 4),
                               total(secure, 1) - total(plain, 1),
                               rtol=1e-9)


def test_plan_from_arch_reads_secure_flag():
    from repro.configs.base import get_arch
    from repro.runtime.engine import plan_from_arch

    cfg = get_arch("smollm-360m").reduced()
    assert plan_from_arch(cfg, 4, 16).keyx_bytes == 0
    secure_cfg = cfg.with_vertical(dataclasses.replace(
        cfg.vertical, secure_aggregation=True))
    assert plan_from_arch(secure_cfg, 4, 16).keyx_bytes == KEYX_GROUP_BYTES
    assert plan_from_arch(cfg, 4, 16, secure=True).keyx_bytes \
        == KEYX_GROUP_BYTES
