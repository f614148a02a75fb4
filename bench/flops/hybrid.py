"""Model FLOPs per trained token of the ``hybrid`` family (Mamba-2 blocks and
one shared attention block every ``hybrid_period`` layers).

Composed from the two families' counts: ``n_layers - n_periods`` Mamba-2
blocks as the ``ssm`` family counts them (its heads are ``ssm_heads``), the
shared block's ``n_periods`` applications as the ``dense`` family counts a
layer (a shared weight costs its FLOPs at every use), and the LM head over
the real vocabulary.  Training is forward plus a backward twice its size;
recomputation is not counted.
"""
from bench.flops import dense, ssm


def forward_flops_per_token(cfg, traffic):
    n_periods = cfg["n_layers"] // cfg["hybrid_period"]
    mamba = dict(cfg, n_heads=cfg["ssm_heads"], n_layers=cfg["n_layers"] - n_periods, vocab=0)
    shared = dict(cfg, n_layers=n_periods, vocab=0)
    return (ssm.forward_flops_per_token(mamba, traffic)
            + dense.forward_flops_per_token(shared, traffic) + 2 * cfg["d_model"] * cfg["vocab"])


def flops_per_token(cfg, traffic):
    return 3 * forward_flops_per_token(cfg, traffic)
