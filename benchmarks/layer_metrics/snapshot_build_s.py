"""Seconds in ``CSRSnapshot.from_tables`` (the program's own clock: phase
``hg.snapshot.from_tables`` in its default registry), all in set-up."""

from harness import phase_total


def read(ctx):
    return phase_total.seconds("hg.snapshot.from_tables")
