"""Share of the roofline of the decode program: the least time of the work the
model needs (``work`` of bench/archs/<model_type>.py) over the program's
device time."""
from readers import roofline


def read(ctx):
    return roofline(ctx, "decode")
