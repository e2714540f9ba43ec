"""Device ms per execution of the prefill program of the ops with no program
scope: XLA's own copies, and ops not found in the HLO; from the profiler trace
and the program's HLO (bench/scopes.py)."""
from scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "prefill", "unscoped")
