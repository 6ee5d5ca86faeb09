"""What the program's ``obs.phase`` sites recorded in this process."""

from __future__ import annotations


def seconds(name: str) -> float | None:
    """The summed seconds of phase ``name`` (histogram ``phase.<name>`` of
    the program's default registry); None where the program records no
    such phase."""
    from hypergraphdb_tpu.obs import default_registry

    hist = default_registry().get(f"phase.{name}")
    return None if hist is None or not hist.count else hist.total
