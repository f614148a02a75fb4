"""Model FLOPs per trained token of the ``dense`` family (GQA decoder).

Counted as the algorithm needs them, from the configuration's sizes: the
q/k/v/o projections, causal attention (a position attends to itself and the
positions before it, (S + 1) / 2 on average, for the scores and for the
values), the SwiGLU MLP, and the LM head over the real vocabulary.  Training
is forward plus a backward twice its size; recomputation is not counted.
"""


def forward_flops_per_token(cfg, traffic):
    d, h, kv, hd, ff = (cfg[k] for k in ("d_model", "n_heads", "n_kv_heads", "head_dim",
                                          "d_ff"))
    seq = traffic["seq_len"]
    proj = 2 * d * (h * hd) * 2 + 2 * d * (kv * hd) * 2
    attn = 2 * 2 * h * hd * (seq + 1) / 2
    mlp = 3 * 2 * d * ff
    return cfg["n_layers"] * (proj + attn + mlp) + 2 * d * cfg["vocab"]


def flops_per_token(cfg, traffic):
    return 3 * forward_flops_per_token(cfg, traffic)
