"""The readers of the program's own instrumentation (:mod:`bench.spans`):
on a hand-made trace with known answers, on a scoped excerpt of a chip
trace of this benchmark, and on traces that lack the instrumentation."""
import glob
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness, spans, trace  # noqa: E402

KERNEL = "%k = f32[4] custom-call(), " + harness.load_module(
    "metrics", "kernel_ms").KERNEL_MARK
READERS = ("bind_ms", "assemble_ms", "host_rebind_ms", "dispatch_ms", "d2h_ms",
           "bind_fill")
# The host's steps of a product; spgemm.execute's own time is none of them.
HOST_STEPS = ("spgemm.rebind", "spgemm.dispatch", "spgemm.wait", "spgemm.d2h",
              "spgemm.collect")
# Apart from bench/testdata's own excerpts, which test_bench_trace.py holds
# to bench.trace.reduce's naming of a whole idle gap by its midpoint: between
# two products of this excerpt the midpoint falls outside bench.execute
# (bench.spans.idle_by_span splits such a gap instead).
SCOPED = sorted(glob.glob(os.path.join(ROOT, "bench", "testdata", "scoped", "*.json")))


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


def _handmade():
    """One product: the host rebinds A and B, dispatches, and collects
    (wait, then the copy); the device binds, runs the kernel, assembles,
    and copies an operand outside any scope."""
    return {
        "devices": {"/device:TPU:0": [
            ["fusion", 10, 20], ["fusion.1", 30, 10], [KERNEL, 40, 5],
            ["fusion.2", 45, 10], ["copy-done", 62, 2]]},
        "scopes": {"/device:TPU:0": [
            "spgemm.bind", "spgemm.bind", "spgemm.kernel", "spgemm.assemble", None]},
        "host": [["bench.window", 0, 100], ["bench.execute", 0, 70]],
        "spans": [
            ["spgemm.execute", 1, 68, {"step": 1}],
            ["spgemm.rebind", 2, 4, {"step": 1, "operand": "a", "values": 5, "slots": 100}],
            ["spgemm.rebind", 6, 3, {"step": 1, "operand": "b", "values": 5, "slots": 100}],
            ["spgemm.dispatch", 9, 1, {"step": 1, "h2d_bytes": 40, "bind_values": 10,
                                       "bind_slots": 200}],
            ["spgemm.collect", 55, 13, {"step": 1}],
            ["spgemm.wait", 55, 4, {"step": 1}],
            ["spgemm.d2h", 59, 6, {"step": 1, "d2h_bytes": 80}],
        ],
    }


def _ctx(events, completed):
    return {"trace": trace.reduce(events), "program": spans.reduce(events),
            "completed": completed}


def test_readings_of_a_handmade_trace():
    events = _handmade()
    red = spans.reduce(events)
    assert red["scope_s"] == pytest.approx(
        {"spgemm.bind": 30e-9, "spgemm.kernel": 5e-9, "spgemm.assemble": 10e-9})
    assert red["span_s"] == pytest.approx({
        "spgemm.execute": 68e-9, "spgemm.rebind": 7e-9, "spgemm.dispatch": 1e-9,
        "spgemm.collect": 13e-9, "spgemm.wait": 4e-9, "spgemm.d2h": 6e-9})
    assert red["span_args"]["spgemm.dispatch"] == {
        "h2d_bytes": 40, "bind_values": 10, "bind_slots": 200}
    assert red["span_args"]["spgemm.rebind"] == {"values": 10, "slots": 200}
    # Idle gaps [0, 10], [55, 62] and [64, 100], each instant by the
    # innermost span: [0, 1] bench.execute, [1, 2] spgemm.execute, [2, 9]
    # the rebinds, [9, 10] dispatch; [55, 59] wait, [59, 62] d2h; [64, 65]
    # d2h, [65, 68] collect, [68, 69] spgemm.execute, [69, 70]
    # bench.execute, [70, 100] none.
    assert dict(red["idle_gaps"]) == pytest.approx({
        "bench.execute": 2e-9, "spgemm.execute": 2e-9, "spgemm.rebind": 7e-9,
        "spgemm.dispatch": 1e-9, "spgemm.wait": 4e-9, "spgemm.d2h": 4e-9,
        "spgemm.collect": 3e-9, "(no bench span)": 30e-9})
    ctx = _ctx(events, 2)
    per = 1e3 / 2
    assert _read("bind_ms", ctx) == pytest.approx(30e-9 * per)
    assert _read("assemble_ms", ctx) == pytest.approx(10e-9 * per)
    assert _read("host_rebind_ms", ctx) == pytest.approx(7e-9 * per)
    assert _read("dispatch_ms", ctx) == pytest.approx(1e-9 * per)
    assert _read("d2h_ms", ctx) == pytest.approx(6e-9 * per)
    assert _read("bind_fill", ctx) == pytest.approx(5.0)
    assert _read("kernel_ms", ctx) == pytest.approx(5e-9 * per)


def test_the_benchmarks_own_reduction_is_unchanged():
    """Everything :func:`bench.trace.reduce` reports reads the same from
    :func:`bench.spans.reduce` but the idle gaps, which the program's
    spans split more finely; with no program spans the split names the
    benchmark's spans, and the total is the same."""
    events = _handmade()
    plain = trace.reduce({"devices": events["devices"], "host": events["host"]})
    assert trace.reduce(events) == plain
    red = spans.reduce(events)
    assert {k: v for k, v in red.items() if k in plain and k != "idle_gaps"} == {
        k: v for k, v in plain.items() if k != "idle_gaps"}
    assert dict(plain["idle_gaps"]) == pytest.approx(
        {"bench.execute": 17e-9, "(no bench span)": 36e-9})
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(53e-9)
    bare = dict(events, spans=[])
    assert dict(spans.reduce(bare)["idle_gaps"]) == pytest.approx(
        {"bench.execute": 23e-9, "(no bench span)": 30e-9})


def test_waits_account_each_product_turn():
    """Window [0, 100]: the one wait, [55, 59], finds the device idle
    throughout, and ends 4 after the assembly ([45, 55]); the turn since
    the window opened, 59 long, holds 45 of device work ([10, 55])."""
    (row,) = spans.waits(_handmade())
    assert row[0] == 1
    assert row[1:] == pytest.approx([59e-6, 4e-6, 4e-6, 4e-6, 59e-6, 45e-6])
    unscoped = _handmade()
    unscoped["scopes"]["/device:TPU:0"] = [None] * 5
    assert spans.waits(unscoped)[0][4] is None
    events = _handmade()
    events["host"][0] = ["bench.window", 0, 58]  # the wait ends after it
    assert spans.waits(events) == []


def test_report_prints_the_split_and_the_waits(capsys):
    spans.report(_handmade())
    out = capsys.readouterr().out
    assert "window 0.000 ms" in out
    assert "x2    spgemm.rebind" in out
    assert "spgemm.dispatch: {'h2d_bytes': 40, 'bind_values': 10, 'bind_slots': 200}" in out
    assert "0.000  (no bench span)" in out
    assert out.rstrip().splitlines()[-1].split() == [
        "1", "0.000", "0.000", "0.000", "0.000", "0.000", "0.000"]


def test_spans_outside_the_window_are_clipped_or_left_out():
    events = _handmade()
    events["host"][0] = ["bench.window", 5, 50]  # [5, 55]
    red = spans.reduce(events)
    assert red["span_s"]["spgemm.rebind"] == pytest.approx(4e-9)  # [5, 6] + [6, 9]
    assert "spgemm.wait" not in red["span_s"]
    # Arguments count the spans that start in the window.
    assert red["span_args"]["spgemm.rebind"] == {"values": 5, "slots": 100}
    assert red["scope_s"]["spgemm.bind"] == pytest.approx(30e-9)


def test_readers_without_a_trace_or_its_instrumentation_read_nothing():
    for metric in READERS:
        assert _read(metric, {"trace": None, "completed": 3}) is None
    # A program without spans or scopes (the same trace, uninstrumented).
    events = _handmade()
    events["spans"] = []
    events["scopes"] = {p: [None] * len(ops) for p, ops in events["devices"].items()}
    ctx = _ctx(events, 2)
    for metric in READERS:
        assert _read(metric, ctx) is None
    assert _read("kernel_ms", ctx) is not None


def test_excerpt_keeps_scopes_and_program_spans():
    ex = spans.excerpt(_handmade(), "bench.execute", 1)
    assert ex["host"] == [["bench.window", 0, 70], ["bench.execute", 0, 70]]
    assert ex["scopes"]["/device:TPU:0"] == [
        "spgemm.bind", "spgemm.bind", "spgemm.kernel", "spgemm.assemble", None]
    assert len(ex["spans"]) == 7
    assert spans.reduce(ex)["scope_s"]["spgemm.bind"] == pytest.approx(30e-9)


def test_recorded_scoped_chip_trace():
    """A scoped excerpt of a chip trace of the single cell (bench/testdata):
    the six readings as recorded, the three scopes covering nearly all of
    the device's busy time, and the host's idle time named by the
    program's spans."""
    assert SCOPED
    for path in SCOPED:
        with open(path) as f:
            rec = json.load(f)
        events = rec["events"]
        ctx = _ctx(events, rec["products"])
        for metric in READERS:
            assert _read(metric, ctx) == pytest.approx(rec["expect"][metric], rel=1e-9)
        red = ctx["program"]
        staged = sum(red["scope_s"].get(s, 0) for s in
                     ("spgemm.bind", "spgemm.kernel", "spgemm.assemble"))
        assert staged >= 0.97 * red["busy_s"]
        gaps = dict(red["idle_gaps"])
        named = sum(gaps.get(name, 0) for name in HOST_STEPS)
        inside = sum(s for name, s in gaps.items() if name != "(no bench span)")
        assert named >= 0.9 * inside


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[4]{0}}

%fused_computation (param_0: f32[9], param_1: s32[8]) -> f32[8] {
  %param_0 = f32[9]{0} parameter(0)
  %param_1 = s32[8]{0} parameter(1)
  ROOT %gather.1 = f32[8]{0} gather(%param_0, %param_1), metadata={op_name="jit(step)/spgemm.bind/gather"}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[4] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %slice.2 = f32[4]{0} slice(%param_0.1), slice={[0:4]}, metadata={op_name="jit(step)/jit(inner)/spgemm.assemble/slice"}
}

ENTRY %main.9 (vals: f32[8]) -> f32[4] {
  %vals = f32[8]{0} parameter(0), metadata={op_name="vals"}
  %fusion = f32[8]{0} fusion(%vals, %vals), kind=kCustom, calls=%fused_computation
  %spgemm.kernel.1 = f32[8]{0} custom-call(%fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/spgemm.kernel/pallas_call"}
  ROOT %fusion.1 = f32[4]{0} fusion(%spgemm.kernel.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/spgemm.assemble/slice"}
}
"""


def test_op_scopes_of_hlo_text():
    """Each instruction's outermost scope; a fusion without metadata takes
    its fused computation's root's."""
    scopes = spans.op_scopes(HLO)
    assert scopes["fusion"] == "spgemm.bind"
    assert scopes["spgemm.kernel.1"] == "spgemm.kernel"
    assert scopes["fusion.1"] == "spgemm.assemble"
    assert scopes["slice.2"] == "spgemm.assemble"
    assert scopes["vals"] is None and scopes["param_0"] is None


def test_load_reads_program_spans_and_modules_from_an_xplane(tmp_path):
    """A real ``.xplane.pb`` made here on CPU: the benchmark's spans read
    as :func:`bench.trace.load` reads them, the program's spans with their
    arguments, and the HLO of the traced module with its scopes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("spgemm.bind"):
            y = x[jnp.arange(16) % 5] * 2.0
        with jax.named_scope("spgemm.assemble"):
            return y.sum()

    x = jnp.ones(16)
    step(x).block_until_ready()
    trace.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for i in range(2):
                with jax.profiler.TraceAnnotation("spgemm.execute", step=i) as sp:
                    step(x).block_until_ready()
                    sp.set_metadata(d2h_bytes=4)
    finally:
        trace.stop()
    path = trace.newest_xplane(str(tmp_path))
    events = spans.load(path)
    plain = trace.load(path)
    assert events["host"] == plain["host"] and events["devices"] == plain["devices"] == {}
    assert [(n, a) for n, _, _, a in events["spans"]] == [
        ("spgemm.execute", {"step": 0, "d2h_bytes": 4}),
        ("spgemm.execute", {"step": 1, "d2h_bytes": 4})]
    modules = spans.hlo_modules(path)
    (name,) = [n for n in modules if n.startswith("jit_step")]
    found = set(spans.op_scopes(modules[name]).values())
    assert {"spgemm.bind", "spgemm.assemble"} <= found
