"""1 - (union of device-busy intervals) / traced window, in %."""
from readers import idle_share


def read(ctx):
    return idle_share(ctx, "prefill")
