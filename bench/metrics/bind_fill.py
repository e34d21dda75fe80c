"""Block fill of the device bind, in percent: the useful values the bind
gathers over the block slots it fills, summed from the arguments
``bind_values`` and ``bind_slots`` of the program's ``spgemm.dispatch``
spans in the traced window (:mod:`bench.spans`). The rest of the slots
are zero pad that the bind writes and the kernel multiplies."""
from bench import spans

UNIT = "%"


def read(ctx):
    red = spans.of(ctx)
    if red is None:
        return None
    args = red["span_args"].get("spgemm.dispatch", {})
    if not args.get("bind_slots"):
        return None
    return 100.0 * args["bind_values"] / args["bind_slots"]
