"""Device milliseconds per step of the attention core (scope ``attention``):
the scores, the softmax and the weighted values, in the forward, backward
and recomputed passes alike. Mean over the chips; ``bench/attention.py``
joins the trace to the step's scopes."""
from bench import attention


def read(ctx):
    return attention.ms_per_step(ctx)
