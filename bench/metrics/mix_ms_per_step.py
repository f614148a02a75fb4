"""Device milliseconds per step of the gossip mix (scope ``mix``): the plan's
weighted sums, the consensus displacement, the cpsgd mean and the drop gates.
Mean over the chips; ``bench/scopes.py`` joins the trace to the step's scopes."""
from bench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx, "mix")
