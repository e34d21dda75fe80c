"""The chip smoke script's phases, driven small on CPU in interpret mode,
and its refusal to report success anywhere but on a TPU."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
SCALE = 0.005  # 505 x 505, a few dozen triples at tile 128


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ok_lines(text):
    return [ln for ln in text.splitlines() if '"ok": true' in ln]


def test_smoke_phases_pass_in_interpret_mode(chip_smoke, capsys):
    out = chip_smoke.run_smoke(scale=SCALE, backend="pallas_interpret")
    assert out["nnz_c"] > 0
    printed = capsys.readouterr().out
    for phase in ("plan", "execute", "fresh", "batch", "stream", "gateway"):
        assert f"phase {phase}:" in printed
    assert not _ok_lines(printed)


def test_reference_check_catches_a_wrong_value(chip_smoke):
    from repro.spgemm import PlanCache, spgemm_plan

    a, b = chip_smoke.operands(SCALE)
    plan = spgemm_plan(a, b, tile=chip_smoke.TILE, group=chip_smoke.GROUP,
                       backend="jnp", cache=PlanCache(), output="compact")
    ref = chip_smoke.Reference(plan)
    c = plan.execute()
    ref.check(c, plan.a_pattern.val, plan.b_pattern.val)
    c.data = c.data.copy()
    c.data[len(c.data) // 2] += 1.0
    with pytest.raises(AssertionError, match="max"):
        ref.check(c, plan.a_pattern.val, plan.b_pattern.val)


def test_main_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert not _ok_lines(captured.out)
    assert "needs a TPU" in captured.err


def test_script_alone_fails(tmp_path):
    """In a directory that holds only the script, it cannot import the
    program and exits non-zero without a verdict."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert not _ok_lines(out.stdout)


def test_sharded_phase_on_forced_devices(forced_devices):
    out = forced_devices(f"""
import importlib.util, json
spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
res = mod.run_sharded(4, scale={SCALE}, backend="pallas_interpret")
print("RESULT", json.dumps(res))
""", devices=4)
    res = json.loads(out.split("RESULT", 1)[1])
    assert sorted(res["mesh"]) == [0, 1, 2, 3]
    for name, ids in res["shard_devices"].items():
        assert ids == res["mesh"], name
