"""Device ms per execution of the prefill program of the ops under ``embed`` and
``head``; from the profiler trace and the program's HLO (bench/scopes.py)."""
from scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "prefill", "embed_head")
