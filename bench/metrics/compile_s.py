"""Host-clock seconds of set-up span ``compile_s``, recorded by the harness."""


def read(ctx):
    return ctx["cell"].spans.get("compile_s")
