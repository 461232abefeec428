"""Model FLOP/s utilisation of the training step: the traced run's
training tokens per second (summed over chips) times the operations per
token that forward and backward need (``models/<model_type>.py``: no
recomputation, causal attention as its causal half), over the chips' bf16
peak."""


def read(ctx):
    f = ctx["facts"]
    if "tokens_per_s" not in f:
        return None
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * f["tokens_per_s"] * f["flops_per_token"] / peak
