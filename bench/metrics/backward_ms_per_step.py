"""Device milliseconds per step of the model's backward pass: ops under
``fwd_bwd`` and JAX's ``transpose(`` marker, without the recomputed forward
ops. Mean over the chips; ``bench/scopes.py`` joins the trace to the step's
scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "backward")
