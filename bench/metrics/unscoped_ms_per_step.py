"""Device milliseconds per step of the rest of the step module's leaf ops:
instructions under no phase scope, or not named in the step's HLO. Mean over
the chips; ``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "unscoped")
