"""``step.mfu``: the whole split-training step's share of the chip's peak.

Training FLOPs per token of the model as built (``flops_per_token`` of the
configuration's reference module: three times the forward matmuls, nothing
recomputed) times the window's tokens per second, over the chips' bf16
peak.  The program keeps f32 weights and runs its matmuls at the TPU's
default precision, one bf16 pass, so the bf16 peak is the one that bounds
it.
"""


def read(ctx):
    if ctx["peak"] is None:
        return None
    peak = ctx["chips"] * ctx["peak"]["bf16_flops"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak
