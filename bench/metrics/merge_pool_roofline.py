"""``merge_pool_roofline``: the cut merge kernel's share of its roofline.

The least time the chip could take for the ``merge_pool`` calls of the
traced steps, forward and backward, over the device time of the programs
that ran them.  The least time is the larger of the bytes over the HBM
bandwidth and the operations over the peak; for the additive merges it is
the bytes.  The bytes are what the kernels' block specs move (``cost``):
the forward reads the K cuts and writes the merged cut, K+1 cut-sized
arrays; the backward reads the K cuts, the merged cut and its gradient and
writes the K cut gradients, 2K+2.

The device time is that of the whole ``jit_merge_pool`` program, not of
its ``tpu_custom_call`` op alone: XLA copies the kernel's operands into
VMEM (memory space ``S(1)`` in the ops' text) before the call, changing
their layout to the row-major one the kernel asks for, and copies the
result back to HBM in the caller's layout after it.  The call itself reads
and writes VMEM, so only the program as a whole moves the bytes above
through HBM.  The eager executor runs the forward and the backward each as
a program of that name; the custom call's result tells them apart: the
merged cut (rows, d) or the K cut gradients (K, rows, d).
"""
import re

import devtrace

MODULE = "jit_merge_pool("
CALL = "custom-call("
RESULT = re.compile(r"= \w+\[([\d,]*)\]")


def cost(k: int, rows: int, d: int, itemsize: int) -> dict:
    """(bytes, operations) of one additive merge kernel call over K cuts of
    (rows, d), for the forward and the backward; the (K,) f32 mask of live
    cuts is read by both."""
    elems, mask = rows * d, 4 * k
    return {"forward": ((k + 1) * elems * itemsize + mask, k * elems),
            "backward": ((2 * k + 2) * elems * itemsize + mask, k * elems)}


def phase(ops, start: float, end: float):
    """``forward`` or ``backward`` for the program that ran in [start, end),
    by the rank of its custom call's result; None without a custom call."""
    for name, s, _ in ops:
        if start <= s < end and CALL in name:
            m = RESULT.search(name)
            if m:
                return "backward" if m.group(1).count(",") == 2 else "forward"
    return None


def read(ctx):
    tr, peak = ctx["trace"], ctx["peak"]
    if tr is None or peak is None:
        return None
    arch, mix = ctx["arch"], ctx["mix"]
    if arch["vertical"]["merge"] not in ("sum", "avg"):
        return None
    lo, hi = devtrace.window(tr)
    costs = cost(arch["vertical"]["num_clients"], mix["batch"] * mix["seq"],
                 arch["d_model"], 4)
    least = spent = 0.0
    for name, s, d in tr["modules"]:
        if name.startswith(MODULE) and lo <= s < hi:
            which = phase(tr["device"], s, s + d)
            if which is not None:
                nbytes, ops = costs[which]
                least += max(nbytes / peak["hbm_bytes_per_s"],
                             ops / peak["bf16_flops"])
                spent += d * 1e-9
    return 100.0 * least / spent if spent else None
