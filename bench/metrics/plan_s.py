"""Host seconds in ``spgemm_plan``: a plan-store load on a warm checkout, the
symbolic build (and its save) on a cold one."""
UNIT = "s"


def read(ctx):
    return ctx["plan_s"]
