"""Device milliseconds per step of the whole receive side (scope ``decode``): the
decode-axpys, fused or not, and the selects of delivered edges.
``decode_axpy_ms_per_step`` is the fused kernel alone. Mean over the chips;
``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "decode")
