"""Device milliseconds per step of the wire encode (scope ``encode``): each
round's compressed quantity and its encode. Mean over the chips;
``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "encode")
