"""The reduction from a profiler trace to the per-layer metrics and the
breakdown: on a hand-made trace with known answers, on a trace excerpt
recorded on a TPU v5 lite, and the reading of a real ``.xplane.pb`` (made
here on CPU, where it has host spans and no device plane)."""
import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness, trace  # noqa: E402

KERNEL = "%k = f32[4] custom-call(), " + harness.load_module(
    "metrics", "kernel_ms").KERNEL_MARK


def _read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


def _handmade():
    return {
        "devices": {"/device:TPU:0": [
            ["bind", 10, 20], ["assemble", 20, 20], [KERNEL, 50, 20], ["late", 90, 20]]},
        "host": [["bench.window", 0, 100], ["bench.execute", 0, 45],
                 ["bench.wait_for_c", 45, 55], ["bench.stream_next", 46, 2]],
    }


def test_reduction_of_a_handmade_trace():
    red = trace.reduce(_handmade())
    assert red["window_s"] == pytest.approx(100e-9)
    # union [10, 40] + [50, 70] + [90, 100 (clipped)]
    assert red["busy_s"] == pytest.approx(60e-9)
    assert red["op_s"] == pytest.approx(
        {"bind": 20e-9, "assemble": 20e-9, KERNEL: 20e-9, "late": 10e-9})
    # gaps [0, 10] in execute; [40, 50] and [70, 90] in wait_for_c
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.execute": 10e-9, "bench.wait_for_c": 30e-9})
    assert red["device_ops"][0][1] == pytest.approx(20e-9)
    ctx = {"trace": red, "completed": 2}
    assert _read("device_idle_share", ctx) == pytest.approx(40.0)
    assert _read("kernel_ms", ctx) == pytest.approx(20e-9 * 1e3 / 2)


def test_union_and_window_errors():
    assert trace.union([[5, 7], [1, 3], [2, 4], [7, 8]]) == [[1, 4], [5, 8]]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce({"devices": {"d": []}, "host": []})
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce({"devices": {}, "host": [["bench.window", 0, 1]]})


def test_readers_without_a_trace_read_nothing():
    ctx = {"trace": None, "completed": 3, "nnz_c": 10}
    for metric in ("kernel_ms", "device_idle_share", "numeric_roofline"):
        assert _read(metric, ctx) is None


def test_roofline_share_and_unknown_device(capsys):
    from bench import patterns

    ops = patterns.operands({"matrix": "hpcg27", "grid": [3, 4, 2], "operation": "A2"})
    red = {"busy_s": 1e-3, "window_s": 2e-3}
    ctx = {"trace": red, "completed": 1, "nnz_c": 100, "ops": ops,
           "device_kind": "TPU v5 lite"}
    nbytes = 4 * (2 * ops.a.nnz + 100)
    assert _read("numeric_roofline", ctx) == pytest.approx(
        100 * nbytes / 819e9 / 1e-3)
    assert "memory floor binds" in capsys.readouterr().err
    with pytest.raises(KeyError, match="no peaks"):
        _read("numeric_roofline", dict(ctx, device_kind="TPU v9 imaginary"))


def test_recorded_chip_trace():
    """An excerpt of a chip trace of this benchmark (bench/testdata): the
    reduction finds the kernel, a busy share under the window, and idle
    time attributed to the benchmark's spans."""
    files = sorted(glob.glob(os.path.join(ROOT, "bench", "testdata", "*.json")))
    assert files
    for path in files:
        with open(path) as f:
            rec = json.load(f)
        red = trace.reduce(rec["events"])
        assert red["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
        assert red["window_s"] == pytest.approx(rec["expect"]["window_s"], rel=1e-9)
        # The union of op intervals again, on a 1 us grid.
        w0, w1 = next((s, s + d) for n, s, d in rec["events"]["host"]
                      if n == trace.WINDOW_SPAN)
        grid = np.zeros((w1 - w0) // 1000 + 1, bool)
        for _, s, d in next(iter(rec["events"]["devices"].values())):
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                grid[(lo - w0) // 1000:(hi - w0) // 1000] = True
        assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=1e-3)
        ctx = {"trace": red, "completed": rec["products"]}
        assert _read("kernel_ms", ctx) == pytest.approx(rec["expect"]["kernel_ms"], rel=1e-9)
        assert 0 < _read("device_idle_share", ctx) < 100
        assert len(red["device_ops"]) <= trace.TOP and len(red["idle_gaps"]) <= trace.TOP
        assert all(name.startswith("bench.") for name, _ in red["idle_gaps"])


def test_load_reads_benchmark_spans_from_an_xplane(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.execute"):
                    f(x).block_until_ready()
    finally:
        trace.stop()
    events = trace.load(trace.newest_xplane(str(tmp_path)))
    names = [h[0] for h in events["host"]]
    assert names.count("bench.execute") == 3 and names.count(trace.WINDOW_SPAN) == 1
    window = next(h for h in events["host"] if h[0] == trace.WINDOW_SPAN)
    for name, start, dur in events["host"]:
        assert window[1] <= start and start + dur <= window[1] + window[2]
    assert events["devices"] == {}  # CPU: no TPU plane


def test_excerpt_keeps_what_overlaps_its_spans():
    ex = trace.excerpt(_handmade(), "bench.execute", 1)
    assert ex["host"] == [["bench.window", 0, 45], ["bench.execute", 0, 45]]
    assert [op[0] for op in ex["devices"]["/device:TPU:0"]] == ["bind", "assemble"]
    assert trace.reduce(ex)["busy_s"] == pytest.approx(30e-9)
