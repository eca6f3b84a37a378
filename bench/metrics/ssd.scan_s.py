"""``ssd.scan_s``: device seconds of the SSD chunk scans per traced step.

The program's chunked scan (``models/mamba.py::ssd_chunked``) compiles to
an XLA ``while`` loop over the chunks that carries the (batch, heads,
head_dim, d_state) f32 state; its forward, and in the backward its
transpose with each chunk recomputed, run inside the layer scan of the
server's or a tower's program.  The device trace names every op by its HLO
text, so the chunk scans are the ``while`` ops whose tuple holds a state of
the server's or the towers' shape.  A loop that holds such a chunk loop
(a layer scan that carries an array of that shape) is left out, so that no
time counts twice.  Summed over the traced window, over its ``bench.step``
spans.  The host span ``ssd.scan`` covers only the scan's tracing.
"""
import re

import devtrace

WHILE = " while("


def state_shapes(arch: dict, batch: int) -> list[tuple]:
    """The SSD state's shape in the server's blocks and in the towers'."""
    s = arch["ssm"]
    widths = (arch["d_model"], arch["d_model"] // arch["vertical"]["num_clients"])
    return [(batch, s["expand"] * d // s["head_dim"], s["head_dim"],
             s["d_state"]) for d in widths]


def chunk_loops(ops, shapes):
    """The (start, end) of the chunk-scan loops among ``ops``."""
    pats = [re.compile(r"[(, ]f32\[%s\]" % ",".join(map(str, shape)))
            for shape in shapes]
    hits = [(a, b) for name, a, b in ops
            if WHILE in name and any(p.search(name) for p in pats)]
    return [(a, b) for a, b in hits
            if not any(a <= c and d <= b and (c, d) != (a, b)
                       for c, d in hits)]


def read(ctx):
    tr, arch = ctx["trace"], ctx["arch"]
    if tr is None or not arch.get("ssm"):
        return None
    lo, hi = devtrace.window(tr)
    ops = [(n, max(s, lo), min(s + d, hi)) for n, s, d in tr["device"]
           if min(s + d, hi) > max(s, lo)]
    loops = chunk_loops(ops, state_shapes(arch, ctx["mix"]["batch"]))
    steps = sum(1 for n, s, d in tr["host"]
                if n == devtrace.STEP and min(s + d, hi) > max(s, lo))
    if not loops or not steps:
        return None
    return sum(b - a for a, b in loops) * 1e-9 / steps
