"""Host clock around enable_incremental's first pack, in set-up."""


def read(ctx):
    return ctx["setup"].get("first_pack_s")
