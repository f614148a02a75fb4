"""Set-up time: from the start of the process to the start of the measured
window (imports, building and compiling the step, making the weights and the
state, the first steps and their readings, warm-up)."""


def read(ctx):
    return ctx.setup_s
