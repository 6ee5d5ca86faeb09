"""The share of the rows a connected-components round folds whose label it
lowers, in percent: the program's counters ``wcc.rows_lowered`` over
``wcc.rows_folded`` (the plan's listed rows, once a round), over the
process. What a round driven by the rows that changed could skip is the
rest. None under a program without the counters."""


def read(ctx):
    from hypergraphdb_tpu.obs import default_registry

    reg = default_registry()
    lowered, folded = (reg.get("wcc.rows_lowered"),
                       reg.get("wcc.rows_folded"))
    if lowered is None or folded is None or not folded.value:
        return None
    return 100.0 * lowered.value / folded.value
