"""Device time of the input pipeline's jitted program (``_node_batches`` of
``data/pipeline.py``, found by its module name) per step, summed over the
chips."""
from bench import trace as T

MODULE = r"jit__node_batches\b"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    ns = sum(T.module_time(d, tr.window, MODULE) for d in tr.devices)
    return ns * 1e-6 / ctx.traced_steps if ns > 0 else None
