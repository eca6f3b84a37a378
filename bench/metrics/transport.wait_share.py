"""``transport.wait_share``: the share of role 0's step spent blocked on the
transport, waiting for the towers' cuts and their ``step_done``: seconds of
the ``transport.wait`` spans over the seconds of the traced steps."""
import spans


def read(ctx):
    got = spans.totals(ctx["trace"], ("transport.wait",))
    return None if got is None else 100.0 * got["span_s"] / got["step_s"]
