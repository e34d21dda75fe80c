"""Device milliseconds of the output assembly per product: the ops under
the program's ``spgemm.assemble`` named scope (the gather of C's values
out of the kernel's panels) in the traced window, over the products
completed in it (:mod:`bench.spans`)."""
from bench import spans

UNIT = "ms"


def read(ctx):
    return spans.per_product_ms(ctx, "scope_s", "spgemm.assemble")
