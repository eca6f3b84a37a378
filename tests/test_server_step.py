"""Role 0's server step is a compiled program: the server forward, the loss
and their gradients with respect to the server params and the merged cut,
traced once per input shape (``Executor.traces``), with the merge run
eagerly under ``jax.vjp`` around it.  It must compute what an eager
``jax.value_and_grad`` of the same server loss computes, return no logits,
and leave one microbatch's gradients as the program gave them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.configs.vertical_mlp import MLPSplitConfig
from repro.core import split_model, towers
from repro.data.loader import LMBatchLoader
from repro.models import backbone, split_program
from repro.runtime.executor import Executor, fast_merge, tree_mean
from repro.transport import SimTransport, TowerWorker

FAMILIES = [("dense", "smollm-360m"), ("ssm", "mamba2-1.3b")]

TINY = MLPSplitConfig(
    name="server_step_tiny", input_dim=16, num_classes=2, num_clients=2,
    client_feature_sizes=(8, 8), tower_hidden=(16,), cut_dim=8,
    server_hidden=(16,), merge="avg",
)


class _JacRecorder(TowerWorker):
    """A tower worker that keeps every jacobian role 0 sends it."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.jacs = []

    def handle(self, request):
        if request["op"] == "backward":
            self.jacs.append(request["jac"])
        return super().handle(request)


def _family(arch, batch=2, seq=16):
    cfg = get_arch(arch).reduced()
    program = split_program.get_program(cfg)
    towers_p, server = program.partition(
        backbone.init_params(cfg, jax.random.PRNGKey(0)))
    raw = next(iter(LMBatchLoader(cfg, batch, seq, seed=0)))
    return program, towers_p, server, {k: jnp.asarray(v)
                                       for k, v in raw.items()}


def _executor(program, towers_p, microbatches=1):
    workers = [_JacRecorder(k, program.tower_fwd(k), towers_p[k])
               for k in range(program.num_clients)]
    tr = SimTransport(workers)
    executor = Executor(tr, program.server_fwd, program.loss_fn,
                        program.merge,
                        microbatches=microbatches, **program.executor_kwargs)
    return executor, workers, tr


def _assert_trees_close(a, b, atol=1e-5):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_allclose(la, lb, atol=atol, rtol=1e-4)


@pytest.mark.parametrize("family,arch", FAMILIES)
def test_server_program_traced_once_per_shape(family, arch):
    program, towers_p, server, b = _family(arch)
    assert program.cfg.family == family
    executor, _, tr = _executor(program, towers_p)
    try:
        for step in range(2):
            executor.run_step(server, program.batch_ctx(b), step=step,
                              features=program.features(b),
                              collect_grads=False)
        assert executor.traces == {"server_step": 1, "mean": 0}
        # one row fewer: a new microbatch shape traces the program once more
        half = {k: v[:1] for k, v in b.items()}
        for step in (2, 3):
            executor.run_step(server, program.batch_ctx(half), step=step,
                              features=program.features(half),
                              collect_grads=False)
        assert executor.traces == {"server_step": 2, "mean": 0}
    finally:
        tr.close()


@pytest.mark.parametrize("family,arch", FAMILIES)
def test_server_step_matches_eager_value_and_grad(family, arch):
    program, towers_p, server, b = _family(arch)
    ctx, feats = program.batch_ctx(b), program.features(b)
    executor, workers, tr = _executor(program, towers_p)
    try:
        res = executor.run_step(server, ctx, features=feats,
                                collect_grads=False)
    finally:
        tr.close()

    stacked = jnp.stack([program.tower_fwd(k)(towers_p[k], feats[k])
                         for k in range(program.num_clients)])

    def server_loss(sp, cuts):
        merged = fast_merge(cuts, program.merge)
        return program.loss_fn(program.server_fwd(sp, merged), ctx)

    loss, (sg, cut_grads) = jax.value_and_grad(
        server_loss, argnums=(0, 1))(server, stacked)
    np.testing.assert_allclose(res.loss, loss, atol=1e-5, rtol=1e-5)
    _assert_trees_close(res.server_grads, sg)
    _assert_trees_close([w.jacs[0] for w in workers], list(cut_grads))


@pytest.mark.parametrize("family,arch", FAMILIES)
def test_server_program_returns_no_logits(family, arch):
    program, towers_p, server, b = _family(arch)
    ctx = program.batch_ctx(b)
    executor, _, tr = _executor(program, towers_p)
    tr.close()
    merged = jax.ShapeDtypeStruct(
        (2, 16, program.cfg.d_model), jnp.float32)
    logits = jax.eval_shape(program.server_fwd, server, merged)
    outs = jax.tree_util.tree_leaves(
        jax.eval_shape(executor._server_step, server, merged, ctx))
    assert logits.shape not in [o.shape for o in outs]


@pytest.mark.parametrize("microbatches", [1, 2])
def test_microbatch_mean_of_the_program_gradients(microbatches):
    """One microbatch's loss and gradients are the program's own arrays;
    two are averaged in one compiled mean."""
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), TINY)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (8, TINY.input_dim))
    y = jax.random.randint(ks[1], (8,), 0, TINY.num_classes)
    feats = [x[:, jnp.asarray(s.indices)]
             for s in split_model.feature_slices(TINY)]
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k])
               for k in range(TINY.num_clients)]

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, TINY.num_classes)

    with SimTransport(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, TINY.merge,
                            microbatches=microbatches)
        program, calls = executor._server_step, []

        def recorded(*args):
            calls.append(program(*args))
            return calls[-1]

        executor._server_step = recorded
        res = executor.run_step(params["server"], y, features=feats,
                                collect_grads=False)
    assert len(calls) == microbatches
    losses = [c[0][0] for c in calls]
    grads = [c[1][0] for c in calls]
    if microbatches == 1:
        assert res.loss is losses[0]
        for got, want in zip(jax.tree_util.tree_leaves(res.server_grads),
                             jax.tree_util.tree_leaves(grads[0]),
                             strict=True):
            assert got is want
        assert executor.traces["mean"] == 0
    else:
        np.testing.assert_allclose(res.loss, sum(losses) / 2, rtol=1e-7)
        for got, want in zip(jax.tree_util.tree_leaves(res.server_grads),
                             jax.tree_util.tree_leaves(tree_mean(grads)),
                             strict=True):
            np.testing.assert_allclose(got, want, rtol=1e-7, atol=0)
        assert executor.traces["mean"] == 1
