"""Device time of the prefill program per execution, from the profiler trace."""
from readers import step_ms


def read(ctx):
    return step_ms(ctx, "prefill")
