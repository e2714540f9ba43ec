"""Device ms per execution of the decode program of the ops labelled ``layers``
outside any block scope: the scan's slicing of the stacked weights and cache,
and its stacking of outputs; from the profiler trace and the program's HLO
(bench/scopes.py)."""
from scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "decode", "scan")
