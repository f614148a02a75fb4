"""Device milliseconds per step of the forward ops that the backward pass
recomputes: ops under ``fwd_bwd`` and JAX's ``rematted_computation`` marker.
Mean over the chips; ``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "remat")
