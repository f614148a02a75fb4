"""Device milliseconds per step of the optimizer (scope ``optimizer``): its
update, the learning rate and every ``apply_updates``. Mean over the chips;
``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "optimizer")
