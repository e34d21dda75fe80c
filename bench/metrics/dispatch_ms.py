"""Host milliseconds of dispatch per product: the program's
``spgemm.dispatch`` spans (the operands' H2D plus the enqueue of the
device programs) in the traced window, over the products completed in it
(:mod:`bench.spans`)."""
from bench import spans

UNIT = "ms"


def read(ctx):
    return spans.per_product_ms(ctx, "span_s", "spgemm.dispatch")
