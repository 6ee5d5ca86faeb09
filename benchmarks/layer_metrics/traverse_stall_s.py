"""Seconds of the window lost to stalls the program flagged as they
happened (``obs.phase``: an instance far over its name's median that
compiled nothing): over the flagged records, wall less that name's median
wall in the window. 0 in a window without one; None under a program that
keeps no record of a phase instance."""

from harness import phase_log


def read(ctx):
    return phase_log.stall_s(ctx)
