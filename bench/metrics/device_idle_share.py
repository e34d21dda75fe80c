"""Share of the traced window in which no op ran on the device:
``100 * (1 - busy / window)``, busy being the union of the device's op
intervals (averaged over the chips used)."""
UNIT = "%"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
