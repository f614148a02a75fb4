"""Device memory the compiled step program needs on its fullest chip, from
the compiler's memory analysis: arguments + outputs - aliased + temporaries.
(The TPU's ``peak_bytes_in_use`` leaves a program's temporaries out.)"""
from bench import hlo


def read(ctx):
    return hlo.program_bytes(ctx.compiled) / 1e9
