"""Host milliseconds of the value rebind per product: the program's
``spgemm.rebind`` spans (the host scatter of each operand's values into
its block array, on the path that rebinds on the host) in the traced
window, over the products completed in it (:mod:`bench.spans`)."""
from bench import spans

UNIT = "ms"


def read(ctx):
    return spans.per_product_ms(ctx, "span_s", "spgemm.rebind")
