"""``executor.server_s``: seconds a step that role 0 spends in its server
step, the ``executor.server_step`` spans (the merge, the server forward and
backward, once per microbatch) per traced step."""
import spans


def read(ctx):
    got = spans.totals(ctx["trace"], ("executor.server_step",))
    return None if got is None else got["span_s"] / got["steps"]
