"""The program's own spans in a profiler trace, per traced step.

The program opens ``jax.profiler.TraceAnnotation`` spans at the split
step's layer boundaries: on role 0's thread ``executor.server_step`` (merge,
server forward and backward), ``executor.jac_fanout`` and
``transport.wait`` (role 0 blocked on the transport); on each tower
worker's thread ``tower.forward``, ``tower.backward`` and ``tower.update``.
``devtrace.load`` keeps their names and times among the host events; the
readers of ``metrics/`` sum them here, clipped to the harness's window, and
count the window's steps as the harness's ``bench.step`` spans in it.
"""
from __future__ import annotations

import devtrace

TOWER = ("tower.forward", "tower.backward", "tower.update")


def totals(trace, names) -> dict | None:
    """Seconds of the spans named in ``names`` inside the traced window,
    with the seconds and the number of the ``bench.step`` spans there.
    None without a trace, or where the window holds none of the spans or
    no step."""
    if trace is None:
        return None
    lo, hi = devtrace.window(trace)
    span_ns = step_ns = 0.0
    spans = steps = 0
    for name, s, d in trace["host"]:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        if name in names:
            span_ns += b - a
            spans += 1
        elif name == devtrace.STEP:
            step_ns += b - a
            steps += 1
    if not spans or not steps:
        return None
    return {"span_s": span_ns * 1e-9, "step_s": step_ns * 1e-9,
            "steps": steps}
