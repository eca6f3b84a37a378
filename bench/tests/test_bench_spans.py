"""The readers of the program's spans on a small recorded trace (no chip
needed): ``executor.server_s``, ``transport.wait_share``, ``tower.busy_s``,
and the idle gaps they take from JAX's own events."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402
import spans  # noqa: E402

CTX = {"arch": {"vertical": {"num_clients": 2}}}


def _load(name):
    doc = json.loads((HERE / name).read_text())
    return {k: [tuple(e) for e in doc[k]] for k in ("device", "modules", "host")}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        HERE.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def tr():
    return _load("trace_spans.json")


def test_totals_clip_to_the_window_and_count_its_steps(tr):
    # the step at 5000-6000 and the server step at 5100 lie past the
    # window's end; client 0's first forward (900-1300) counts from 1000
    got = spans.totals(tr, ("executor.server_step",))
    assert got["steps"] == 2
    assert got["step_s"] == pytest.approx(4000e-9)
    assert got["span_s"] == pytest.approx(1600e-9)
    assert spans.totals(tr, spans.TOWER)["span_s"] == pytest.approx(
        (300 + 300 + 100 + 400 + 300 + 100  # client 0
         + 500 + 350 + 80 + 500 + 350 + 80) * 1e-9)  # client 1


@pytest.mark.parametrize("metric, want", [
    ("executor.server_s", 800e-9),  # (800 + 800) ns over 2 steps
    ("transport.wait_share", 100 * (600 + 450 + 600 + 450) / 4000),
    ("tower.busy_s", 3360e-9 / 2 / 2),  # over 2 steps and K=2 towers
])
def test_span_readers(tr, metric, want):
    read = _reader(metric)
    assert read({**CTX, "trace": tr}) == pytest.approx(want)
    assert read({**CTX, "trace": None}) is None
    # a trace from a program without spans: no reading, not a zero
    assert read({**CTX, "trace": _load("trace_small.json")}) is None


def test_idle_gaps_go_to_the_program_spans_not_the_jax_events_inside(tr):
    # gaps 1000-1300 (client 0's forward, earliest of three that overlap
    # 300 ns), 1400-2350 and 3500-4350 (server step, 750 ns of each against
    # its nested scan's 700), 2400-2980, 3000-3400 and 4400-4980 (role 0
    # waiting on the transport)
    got = dict(devtrace.idle_gaps(tr, 1000, 5000))
    assert got == pytest.approx({"executor.server_step": 1800e-9,
                                 "transport.wait": 1560e-9,
                                 "tower.forward": 300e-9})
    # the same trace without the program's spans: the nested JAX events
    # take the gaps, as they did before the program had spans
    program = set(spans.TOWER) | {"executor.server_step",
                                  "executor.jac_fanout", "transport.wait"}
    bare = {**tr, "host": [e for e in tr["host"] if e[0] not in program]}
    assert dict(devtrace.idle_gaps(bare, 1000, 5000)) == pytest.approx({
        "PjitFunction(scan)": 2380e-9, "PjitFunction(multiply)": 580e-9,
        "host: no event": 400e-9, "PjitFunction(tower)": 300e-9})
