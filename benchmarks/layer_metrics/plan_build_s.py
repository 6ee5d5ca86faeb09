"""Host clock around ops.ellbfs.plans_for (build, or load from HG_PLAN_CACHE), in set-up."""


def read(ctx):
    return ctx["setup"].get("plan_build_s")
