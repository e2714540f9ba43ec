"""Device ms per execution of the prefill program of the ops under the channel
mixer's scope (``ffn``, ``moe``); from the profiler trace and the program's HLO
(bench/scopes.py)."""
from scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "prefill", "ffn")
