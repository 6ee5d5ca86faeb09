"""Host clock around ServeRuntime's start (compiles or AOT loads), in set-up."""


def read(ctx):
    return ctx["setup"].get("runtime_start_s")
