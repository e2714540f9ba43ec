"""Host-clock seconds of set-up span ``solve_s``, recorded by the harness."""


def read(ctx):
    return ctx["cell"].spans.get("solve_s")
