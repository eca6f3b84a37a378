"""Reduction of a profiler trace to device busy time, op time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into lists of
``(name, start_ns, duration_ns)``: the operations that ran on the first
TPU ("XLA Ops": HLO instructions, named by their text), the compiled
programs they ran in ("XLA Modules": ``jit_<function>(<hash>)``), and the
host events of every host thread.  Device and host events share one clock.
The functions below work on those lists alone, so a test can hand them a
small recorded trace.
"""
from __future__ import annotations

from pathlib import Path

WINDOW = "bench.window"  # the harness's span around the traced steps
STEP = "bench.step"  # the harness's span around each step
OWN = (WINDOW, STEP)


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(paths[-1]))
    out = {"device": [], "modules": [], "host": []}
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    lines = {"XLA Ops": "device", "XLA Modules": "modules"}
    for line in (tpus[0].lines if tpus else []):
        if line.name in lines:
            out[lines[line.name]] += [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
    return out


def window(trace: dict) -> tuple[float, float]:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    if not spans:
        raise RuntimeError(f"the trace holds no {WINDOW!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clipped(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def busy_intervals(trace: dict, lo: float, hi: float) -> list[tuple]:
    """The union of the device's operation intervals inside [lo, hi)."""
    out = []
    for _, a, b in sorted(_clipped(trace["device"], lo, hi),
                          key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(trace: dict, lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy_intervals(trace, lo, hi))


def op_totals(trace: dict, lo: float, hi: float) -> list[tuple[str, float]]:
    """Device seconds per operation name inside [lo, hi), largest first."""
    tot: dict[str, float] = {}
    for name, a, b in _clipped(trace["device"], lo, hi):
        tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])


def idle_gaps(trace: dict, lo: float, hi: float) -> list[tuple[str, float]]:
    """Idle device time inside [lo, hi), each gap put down to the host
    event (other than the harness's own spans) that overlaps it most, and
    summed per event name, largest first."""
    gaps, t = [], lo
    for a, b in busy_intervals(trace, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = sorted((e for e in trace["host"] if e[0] not in OWN),
                  key=lambda e: e[1])
    tot: dict[str, float] = {}
    active, i = [], 0
    for ga, gb in gaps:  # in time order: sweep the host events once
        while i < len(host) and host[i][1] < gb:
            active.append(host[i])
            i += 1
        active = [e for e in active if e[1] + e[2] > ga]
        best, label = 0.0, "host: no event"
        for name, a, b in _clipped(active, ga, gb):
            if b - a > best:
                best, label = b - a, name
        tot[label] = tot.get(label, 0.0) + (gb - ga) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])
