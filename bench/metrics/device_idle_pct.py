"""Share of the traced window in which no instruction ran on a chip, as the
mean over the chips."""
from bench import trace as T


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    win = tr.window[1] - tr.window[0]
    busy = [T.length(T.busy(d, tr.window)) for d in tr.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / win)
