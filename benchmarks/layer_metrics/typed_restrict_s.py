"""Seconds restricting the snapshot to the run's family and building the
restricted plan (the program's own clock: phase ``hg.bfs.restrict`` in its
default registry, once per (snapshot, family)), all in set-up. None under a
program that records no such phase."""

from harness import phase_total


def read(ctx):
    return phase_total.seconds("hg.bfs.restrict")
