"""Model FLOPs utilization of the whole step in the traced window: model
FLOPs per token (``bench/flops/<family>.py``, no recomputation) times the
tokens trained in the window, over its seconds, over chips times the chip's
bf16 peak."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr.window_s <= 0:
        return None
    tokens = ctx.traced_steps * ctx.tokens_per_step
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * ctx.flops_per_token * tokens / tr.window_s / peak
