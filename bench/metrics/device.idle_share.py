"""``device.idle_share``: the share of the traced window in which no
operation ran on the chip, 1 - (union of the device's operation intervals)
/ window, from the profiler trace of the window's first steps."""
import devtrace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["device"]:
        return None
    lo, hi = devtrace.window(tr)
    return 100.0 * (1.0 - devtrace.busy_ns(tr, lo, hi) / (hi - lo))
