"""The split step's spans: ``jax.profiler.TraceAnnotation`` at the layer
boundaries of role 0 (server step, jacobian fan-out, transport wait) and of
the tower workers (forward, backward, local update) land in the profiler's
own trace, each tagged with its step, and no span of the program encloses
another on one host thread."""
import collections
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs.vertical_mlp import MLPSplitConfig
from repro.core import split_model, towers
from repro.runtime import StepPipeline
from repro.runtime.executor import Executor
from repro.transport import InprocTransport, SimTransport, TowerWorker
from repro.transport.builders import _sgd

K, M, STEPS = 2, 2, 2
CFG = MLPSplitConfig(
    name="spans_tiny", input_dim=16, num_classes=2, num_clients=K,
    client_feature_sizes=(8, 8), tower_hidden=(16,), cut_dim=8,
    server_hidden=(16,), merge="avg",
)
SPANS = ("executor.server_step", "executor.jac_fanout", "transport.wait",
         "tower.forward", "tower.backward", "tower.update")


def _rows(step):
    ks = jax.random.split(jax.random.PRNGKey(100 + step), 2)
    x = jax.random.normal(ks[0], (8, CFG.input_dim))
    feats = [x[:, jnp.asarray(s.indices)]
             for s in split_model.feature_slices(CFG)]
    return feats, jax.random.randint(ks[1], (8,), 0, CFG.num_classes)


def _traced_spans(transport_cls, log_dir):
    """Run STEPS split steps under the profiler; returns, per host line,
    the program's spans as (name, start_ns, end_ns, stats)."""
    params = split_model.init_split_mlp(jax.random.PRNGKey(0), CFG)
    workers = [TowerWorker(k, towers.mlp_tower_apply, params["towers"][k],
                           optimizer=_sgd(0.1)) for k in range(K)]

    def loss_fn(logits, labels):
        return split_model.softmax_xent(logits, labels, CFG.num_classes)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    server = params["server"]
    with transport_cls(workers) as tr:
        executor = Executor(tr, towers.mlp_tower_apply, loss_fn, CFG.merge,
                            microbatches=M)
        pipeline = StepPipeline(executor, window=1)
        with jax.profiler.trace(str(log_dir), profiler_options=opts):
            for step in range(STEPS):
                feats, labels = _rows(step)
                res = pipeline.push(server, labels, step=step,
                                    features=feats, collect_grads=False)
                server = jax.tree_util.tree_map(
                    lambda p, g: p - 0.1 * g, server, res.server_grads)
                jax.block_until_ready(server)
    path, = Path(log_dir).rglob("*.xplane.pb")
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                          dict(e.stats))
                         for e in line.events if e.name in SPANS]
                if spans:
                    lines.append(spans)
    return lines


@pytest.fixture(scope="module", params=["inproc", "sim"])
def lines(request, tmp_path_factory):
    transport_cls = {"inproc": InprocTransport, "sim": SimTransport}
    return _traced_spans(transport_cls[request.param],
                         tmp_path_factory.mktemp(request.param))


def test_each_span_appears_as_often_as_the_step_does_its_work(lines):
    per_step = collections.Counter(
        (s[3]["step"], s[0]) for spans in lines for s in spans)
    for step in range(STEPS):
        assert per_step[(step, "executor.server_step")] == M
        assert per_step[(step, "executor.jac_fanout")] == M
        assert per_step[(step, "tower.forward")] == K * M
        assert per_step[(step, "tower.backward")] == K * M
        assert per_step[(step, "tower.update")] == K
        assert per_step[(step, "transport.wait")] >= 1
    assert {step for step, _ in per_step} == set(range(STEPS))


def test_each_span_carries_its_step_and_where_it_ran(lines):
    for spans in lines:
        for name, _, _, stats in spans:
            assert "step" in stats, name
            if name.startswith("tower."):
                assert stats["client"] in range(K), (name, stats)
            if name in ("executor.server_step", "tower.forward"):
                assert stats["mb"] in range(M), (name, stats)


def test_no_program_span_encloses_another_on_one_thread(lines):
    for spans in lines:
        spans = sorted(spans, key=lambda s: s[1])
        for prev, nxt in zip(spans, spans[1:]):
            assert nxt[1] >= prev[2], (prev[:3], nxt[:3])
