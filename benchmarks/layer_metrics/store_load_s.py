"""Host clock around the bulk_import load of the store, in set-up."""


def read(ctx):
    return ctx["setup"].get("store_load_s")
