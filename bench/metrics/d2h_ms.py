"""Host milliseconds of the result's copy to the host per product: the
program's ``spgemm.d2h`` spans (``np.asarray`` of a finished packed C;
the wait for the device is ``spgemm.wait``, apart) in the traced window,
over the products completed in it (:mod:`bench.spans`)."""
from bench import spans

UNIT = "ms"


def read(ctx):
    return spans.per_product_ms(ctx, "span_s", "spgemm.d2h")
