"""Seconds uploading the index plans to the device (the program's own
clock: phase ``hg.bfs.plan.upload`` in its default registry), all in set-up."""

from harness import phase_total


def read(ctx):
    return phase_total.seconds("hg.bfs.plan.upload")
