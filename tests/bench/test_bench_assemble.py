"""The readers of the output assembly's run length and time per value,
on a small recorded-shape trace: two products in the window, each
dispatched once and assembling for 9 ns on the device."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness, spans, trace  # noqa: E402


def _events(dispatch_args):
    return {
        "devices": {"/device:TPU:0": [["fusion", 5, 10], ["assemble", 20, 9],
                                      ["fusion", 55, 10], ["assemble", 70, 9]]},
        "scopes": {"/device:TPU:0": ["spgemm.kernel", "spgemm.assemble"] * 2},
        "host": [["bench.window", 0, 100]],
        "spans": [["spgemm.dispatch", s, 2, dict(dispatch_args, step=i)]
                  for i, s in enumerate((1, 51, 120))],
    }


def _ctx(events, completed=2):
    w0, w1 = trace._window(events)
    return {"trace": trace.reduce(events), "program": spans.reduce(events),
            "completed": completed,
            "dispatches": [a for n, s, _, a in events["spans"]
                           if n == "spgemm.dispatch" and w0 <= s < w1]}


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


@pytest.mark.parametrize("runs, values", [(2_766_953, 36_177_111),
                                          (7_301_384, 7_301_384)])
def test_readers_of_run_length_and_time_per_value(runs, values):
    ctx = _ctx(_events({"assemble_runs": runs, "assemble_values": values}))
    assert _read("assemble_ms", ctx) == pytest.approx(9e-6)
    assert _read("assemble_run_len", ctx) == pytest.approx(values / runs)
    # 9 ns of assembly a product over its values, in nanoseconds.
    assert _read("assemble_value_ns", ctx) == pytest.approx(9.0 / values)


def test_readers_find_nothing_without_the_counters():
    """A program whose spans lack the counters (the parent's) reads
    nothing, and so does a run without a trace."""
    bare = _ctx(_events({"kernel_calls": 1}))
    for metric in ("assemble_run_len", "assemble_value_ns"):
        assert _read(metric, bare) is None
        assert _read(metric, {"trace": None, "completed": 2}) is None
