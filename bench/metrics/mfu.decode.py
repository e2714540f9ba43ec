"""Model FLOPs the window's decode work needs, over window wall time x chips x
the bf16 peak."""
from readers import mfu


def read(ctx):
    return mfu(ctx, "decode")
