"""Device milliseconds of the value bind per product: the ops under the
program's ``spgemm.bind`` named scope (the gathers of A's and B's fresh
values into every slot of their block arrays) in the traced window, over
the products completed in it (:mod:`bench.spans`)."""
from bench import spans

UNIT = "ms"


def read(ctx):
    return spans.per_product_ms(ctx, "scope_s", "spgemm.bind")
