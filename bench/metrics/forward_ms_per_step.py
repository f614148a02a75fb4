"""Device milliseconds per step of the model's forward pass: ops under
``fwd_bwd`` outside JAX's ``transpose(`` and ``rematted_computation`` markers.
Mean over the chips; ``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "forward")
