"""Process start to the start of the window: patterns and values from the
seed, the plan (a store load, or a build on a cold checkout), device
staging, warm-up and any compilation."""
UNIT = "s"


def read(ctx):
    return ctx["setup_s"]
