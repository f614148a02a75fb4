"""The attention core's share of its roofline: the least time of a step's
causal attention work on one chip (``bench/attention.py``: FLOPs at the bf16
peak or bytes at the HBM bandwidth, whichever is longer), over the time
``attention_ms_per_step`` reads."""
from bench import attention


def read(ctx):
    ms = attention.ms_per_step(ctx)
    if not ms:
        return None
    least = attention.least_s(ctx.cfg, ctx.traffic, ctx.nodes_per_device, ctx.peaks)
    return 100.0 * least / (ms * 1e-3)
