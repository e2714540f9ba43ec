"""Host-clock seconds of set-up span ``init_s``, recorded by the harness."""


def read(ctx):
    return ctx["cell"].spans.get("init_s")
