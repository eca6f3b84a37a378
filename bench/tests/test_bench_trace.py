"""The trace reduction on a small recorded trace (no chip needed)."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import devtrace  # noqa: E402

# window 1000-3000 ns: busy [1100,1250] + [1600,1800] + [2500,2600] = 450 ns
# (the ops at 1150 and 1700 overlap others, the one at 3500 lies outside)


@pytest.fixture(scope="module")
def tr():
    doc = json.loads((HERE / "trace_small.json").read_text())
    return {k: [tuple(e) for e in doc[k]] for k in ("device", "modules", "host")}


def test_window_is_the_harness_span(tr):
    assert devtrace.window(tr) == (1000, 3000)


def test_busy_is_the_union_of_op_intervals(tr):
    assert devtrace.busy_intervals(tr, 1000, 3000) == [
        (1100, 1250), (1600, 1800), (2500, 2600)]
    assert devtrace.busy_ns(tr, 1000, 3000) == 450


def test_op_totals_sum_time_per_name_inside_the_window(tr):
    got = {name.split(" =")[0]: t
           for name, t in devtrace.op_totals(tr, 1000, 3000)}
    assert got == pytest.approx({"%fusion.1": 200e-9, "%dot.2": 200e-9,
                                 "%custom-call.2": 100e-9,
                                 "%custom-call.3": 50e-9,
                                 "%custom-call.7": 10e-9})


def test_idle_gaps_go_to_the_host_event_overlapping_most(tr):
    # gaps 1000-1100 (compile), 1250-1600 (dispatch), 1800-2500 (compile
    # 550 of 700), 2600-3000 (dispatch); the harness's own spans never win
    got = dict(devtrace.idle_gaps(tr, 1000, 3000))
    assert got == pytest.approx({"backend_compile": 800e-9,
                                 "PjitFunction(step)": 750e-9})
    assert sum(got.values()) == pytest.approx((2000 - 450) * 1e-9)


def test_idle_share_reader(tr):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "idle_share", HERE.parent / "metrics" / "device.idle_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read({"trace": tr}) == pytest.approx(100 * 1550 / 2000)
    assert mod.read({"trace": None}) is None
