"""Device time of the decode program per execution, from the profiler trace."""
from readers import step_ms


def read(ctx):
    return step_ms(ctx, "decode")
