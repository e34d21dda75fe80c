"""Every cell driven end to end on CPU on a small grid in interpret mode; the
command's refusal off a TPU; and the faults the check has to catch."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
SEED = 2**31 + 2**20 + 3  # above 32 signed bits, as the check's seeds are


@pytest.fixture
def small(tmp_path):
    return {"grid": [8, 8, 16], "backend": "pallas_interpret",
            "plan_dir": str(tmp_path / "plans")}


def _run(name, small, trace_on=False, plan_hook=None, seconds=0.4):
    return harness.run_cell(harness.resolve(name), SEED, seconds, trace_on,
                            t0=time.perf_counter(), overrides=small,
                            plan_hook=plan_hook)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name, small, capsys):
    cell = harness.resolve(name)
    result = _run(name, small)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["window_compiles"] == 0
    assert set(result["metrics"]) == set(cell.end_to_end)
    for m in result["metrics"].values():
        assert m["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    assert result["checks"]["compared"]["value"] == cell.traffic["check_samples"]
    harness.emit(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert err.strip().splitlines()[-1].startswith("check compared ")


def test_same_seed_same_inputs(small):
    """Two runs of one seed compare the same value sets and read the same
    worst error (the window's length does not change what is drawn)."""
    first = _run("hpcg40-A2.single", small, seconds=0.2)
    again = _run("hpcg40-A2.single", small, seconds=0.2)
    assert first["checks"]["value_err"] == again["checks"]["value_err"]


def _command(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "hpcg40-A2.single", "--seed", str(SEED), "--seconds", "1",
        "--trace", "0"]


def test_command_refuses_without_a_tpu():
    out = _command(ARGS, ROOT)
    assert out.returncode == 2
    assert "needs 1 TPU chip" in out.stderr
    assert '"correct"' not in out.stdout


def test_command_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns(".*", "__pycache__"))
    out = _command(ARGS, tmp_path)
    assert out.returncode != 0
    assert "No module named 'repro'" in out.stderr
    assert '"correct"' not in out.stdout


# -- faults: the timed path broken underneath, correct has to come out false --


def _through(plan, fn):
    """Route every CSR that ``execute`` and ``execute_stream`` hand back
    through ``fn(i, csr)``; a ``None`` from ``fn`` drops that result."""
    execute, stream, count = plan.execute, plan.execute_stream, iter(range(10**9))

    def execute_(*args, **kwargs):
        out = fn(next(count), execute(*args, **kwargs))
        if out is None:
            raise RuntimeError("result dropped")
        return out

    def stream_(values, depth=None):
        for c in stream(values, depth=depth):
            out = fn(next(count), c)
            if out is not None:
                yield out

    plan.execute, plan.execute_stream = execute_, stream_


def _stale(plan):
    """Every product returns the first result (a warm-up's) unchanged."""
    first = {}
    _through(plan, lambda i, c: first.setdefault("c", c))


def _altered_value(plan):
    def alter(i, c):
        data = np.array(c.data)
        data[(i * 7919) % data.shape[0]] *= 1.01
        return type(c)(c.indptr, c.indices, data, c.shape)
    _through(plan, alter)


def _altered_pattern(plan):
    def alter(i, c):
        indices = np.array(c.indices)
        row = int(np.argmax(np.diff(c.indptr) > 1))
        lo = int(c.indptr[row])
        indices[lo], indices[lo + 1] = indices[lo + 1], indices[lo]
        return type(c)(c.indptr, indices, c.data, c.shape)
    _through(plan, alter)


def _half_dropped(plan):
    _through(plan, lambda i, c: c if i < 4 or i % 2 == 0 else None)  # warm-up passes


FAULTS = {"stale_state": _stale, "altered_value": _altered_value,
          "altered_pattern": _altered_pattern, "half_dropped": _half_dropped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ["hpcg40-A2.single", "hpcg40-A2.stream"])
def test_a_broken_path_is_not_correct(name, fault, small):
    result = _run(name, small, plan_hook=FAULTS[fault], seconds=0.3)
    assert result["correct"] is False, (fault, result["checks"])


# -- the sampler and the device's peak --


class _Result:
    def __init__(self, i):
        self.indptr, self.indices = np.array([0, 2]), np.array([0, 1])
        self.data = np.full(2, float(i), np.float32)


@pytest.mark.parametrize("primed", [True, False])
@pytest.mark.parametrize("seed", [1, 2**31 + 7, 12345])
def test_sampler_keeps_copies_in_the_same_buffers_for_every_seed(seed, primed):
    """Every result is copied into one of k + 1 buffers, whatever the seed
    keeps (allocated before the first result when primed, by the k-th
    otherwise); the kept copies hold what was handed in, not what the
    program later does with its own arrays."""
    k = 3
    sampler = harness.Sampler(k, seed)
    if primed:
        sampler.prime(_Result(-1))
    handed, buffers = [], set()
    for i in range(40):
        c = _Result(i)
        handed.append(c)
        sampler(i, i % 8, c)
        if primed or i >= k:
            held = {id(e[2][2]) for e in sampler.kept} | {id(b) for b in sampler._free}
            buffers = buffers or held
            assert held == buffers and len(held) == k + 1
    for c in handed:
        c.data[:] = -1.0
    assert len(sampler.kept) == k
    for index, slot, (_, _, data) in sampler.kept:
        assert slot == index % 8 and np.all(data == index)
    again = harness.Sampler(k, seed)
    for i in range(40):
        again(i, i % 8, _Result(i))
    assert [e[0] for e in again.kept] == [e[0] for e in sampler.kept]


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_device_peak_counts_what_compiled_programs_reserve():
    peak = harness._device_peak_bytes
    assert peak(_Device({"peak_bytes_in_use": 5, "peak_bytes_reserved": 7})) == 12
    assert peak(_Device({"peak_bytes_in_use": 5})) == 5
    assert peak(_Device(None)) is None and peak(_Device({})) is None
