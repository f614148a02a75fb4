"""Tokens trained per second per chip: every node's tokens of every step that
completed in the window, over the window's seconds (from its start to the
last completion), over the chips the cell holds."""


def read(ctx):
    w = ctx.window
    if not w or not w["completions"]:
        return None
    return w["steps"] * ctx.tokens_per_step / (w["completions"][-1] - w["t0"]) / ctx.chips
