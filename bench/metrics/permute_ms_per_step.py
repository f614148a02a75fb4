"""Device milliseconds per step of the payload and model rolls (scope
``permute``): collective-permutes across chips, copies on one chip. Mean over
the chips; ``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "permute")
