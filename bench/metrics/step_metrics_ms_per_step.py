"""Device milliseconds per step of the step's metrics (scope ``step_metrics``):
the consensus sum and the loss and aux means. Mean over the chips;
``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "step_metrics")
