"""Seconds of set-up in the driver's warm-up (``run.py``'s clock around
``driver.warm()``): tracing, lowering, compiling or loading every program
the window will use, and one operation. The part of ``setup_s`` that a
kernel's or a program's TEXT moves — tracing and lowering are paid on every
run, compile cache or no — and that no builder's or plan's span holds."""


def read(ctx):
    return ctx["setup"].get("warm_s")
