"""The decode-axpy kernel's share of its roofline.  The work is what DCD's
receive side needs per step on one chip, computed from the leaf shapes, the
wire and the gossip plan whatever implements it: for each node held there,
(1 + distinct ring neighbours) decode-axpys of every leaf, each moving the
payload plus 4 B read and 4 B written per element (``bench/wire.py``).  The
work moves bytes and does a few operations per element, so the bound is the
chip's HBM bandwidth; the least time at it, over the kernel's measured time."""
from bench import trace as T
from bench import wire as W

KERNEL = r"unpack_dequant_axpy(\.\d+)?$"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    ns = sum(T.op_time(d, tr.window, KERNEL) for d in tr.devices) / len(tr.devices)
    if ns <= 0:
        return None
    per_step = ns * 1e-9 / ctx.traced_steps
    decodes = 1 + W.ring_shifts(ctx.traffic["nodes"])
    work = ctx.nodes_per_device * decodes * W.decode_axpy_bytes(
        ctx.param_shapes, ctx.traffic["wire_bits"])
    return 100.0 * work / ctx.peaks["hbm_bytes_per_s"] / per_step
