"""``tower.busy_s``: seconds a step that one tower worker spends in its
forwards, backwards and local update (the ``tower.*`` spans on the workers'
threads) per traced step, over the K towers.  Wall time inside the spans,
so a worker's wait for the interpreter lock counts."""
import spans


def read(ctx):
    got = spans.totals(ctx["trace"], spans.TOWER)
    if got is None:
        return None
    return got["span_s"] / got["steps"] / ctx["arch"]["vertical"]["num_clients"]
