"""Model FLOPs per trained token of the ``ssm`` family (Mamba-2).

Counted as the algorithm needs them, from the configuration's sizes: the
projections, the depthwise convolution, the SSD map in its chunked form
(per token and layer: the C.B scores of its chunk, shared by the heads of a
group; the chunk's scores times the inputs; the state read by C and written
by B), and the LM head over the real vocabulary.  Training is forward plus
a backward twice its size; recomputation (remat) is not counted.
"""


def forward_flops_per_token(cfg, traffic):
    d, di, n, h, g = (cfg[k] for k in ("d_model", "d_inner", "d_state", "n_heads",
                                         "n_groups"))
    chunk, k = cfg["chunk"], cfg["d_conv"]
    dbc = 2 * g * n
    proj = 2 * d * (2 * di + dbc + h) + 2 * di * d
    conv = 2 * k * (di + dbc)
    ssd = 2 * chunk * n * g + 2 * chunk * di + 4 * n * di
    return cfg["n_layers"] * (proj + conv + ssd) + 2 * d * cfg["vocab"]


def flops_per_token(cfg, traffic):
    return 3 * forward_flops_per_token(cfg, traffic)
