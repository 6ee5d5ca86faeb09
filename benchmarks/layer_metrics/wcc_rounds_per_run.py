"""Rounds a connected-components run: the program's counter ``wcc.rounds``
over ``wcc.runs``, over the process, warm-up included (every run of a cell
asks the same question of the same graph). The longest way from an atom to
its component's least id, plus the quiet round; a round driven by the rows
that changed would leave it, a device-side loop too. None under a program
without the counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    rounds, runs = reg.get("wcc.rounds"), reg.get("wcc.runs")
    if runs is None or not runs.value or rounds is None:
        return None
    return rounds.value / runs.value
