"""Device time of the fused ``unpack_dequant_axpy`` kernel (found by its
stable name) per step, as the mean over the chips."""
from bench import trace as T

KERNEL = r"unpack_dequant_axpy(\.\d+)?$"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    ns = sum(T.op_time(d, tr.window, KERNEL) for d in tr.devices) / len(tr.devices)
    return ns * 1e-6 / ctx.traced_steps if ns > 0 else None
