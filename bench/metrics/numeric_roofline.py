"""Share of the numeric phase's roofline, per product, in percent.

The least time the chip could take for one product (the larger of its
useful FLOPs over peak FLOP/s and its compulsory bytes over peak HBM
bandwidth, :mod:`bench.workcount`, peaks from ``bench/peaks.json``) over
the device-busy time per product in the traced window: every op of the
numeric phase (value bind, kernel, assembly) counts, whatever implements
it. An unknown ``device_kind`` is an error. The floor that binds is
written to stderr.
"""
import json
import os
import sys

from bench.workcount import compulsory_bytes, roofline_floor_s, useful_flops

UNIT = "%"
PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "peaks.json")


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["completed"] or not ctx["nnz_c"] or tr["busy_s"] <= 0:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)
    if ctx["device_kind"] not in peaks:
        raise KeyError(f"no peaks for device kind {ctx['device_kind']!r} in {PEAKS}")
    ops = ctx["ops"]
    floor_s, binds = roofline_floor_s(
        useful_flops(ops), compulsory_bytes(ops, ctx["nnz_c"]), peaks[ctx["device_kind"]])
    print(f"numeric_roofline: {binds} floor binds, {floor_s * 1e6:.3f} us per product",
          file=sys.stderr)
    return 100.0 * floor_s / (tr["busy_s"] / ctx["completed"])
