"""``chip_smoke.py`` on the CPU: its phases at a tiny size, and its refusal.

The smoke's phases (client Phase 1, wire upload, SOLVE frames through the
batcher, solve_report intervals, predict, a coalesced §VI-C stream, Thm-8
drop/restore) run end to end at d=64, K=4, D=128 against the script's own
float64 host reference and tolerances. The test steers the platform check;
the script itself refuses any host without a TPU.
"""
import importlib.util
import json
import pathlib
import sys

import pytest

_SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    module = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", module)
    spec.loader.exec_module(module)
    return module


def test_phases_match_float64_at_tiny_size(monkeypatch, capsys):
    smoke = _load(monkeypatch)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(smoke, "require_tpu", lambda: device)
    monkeypatch.setattr(smoke, "enable_compilation_cache", lambda: "off")
    monkeypatch.setattr(smoke, "ONE_CHIP", smoke.Cell(
        clients=4, rows=64, dim=64, rff_features=128, rff_dim=32, stream=8,
        queries=8))
    assert smoke.main([]) == 0
    out = capsys.readouterr().out
    assert "FAIL" not in out, out
    for phase in ("phase1", "upload", "serve", "stream", "churn"):
        assert f"phase {phase}:" in out, out
    assert json.loads(out.splitlines()[-1]) == {"ok": True, "device": device}


_FOUR_CHIPS_CHILD = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke as smoke
device = {{"platform": "cpu", "kind": "cpu", "count": 4}}
smoke.require_tpu = lambda: device
smoke.enable_compilation_cache = lambda: "off"
smoke.FOUR_CHIPS = smoke.ShardedCell(clients=2, rows=128, dim=256,
                                     update_rows=8)
assert smoke.main(["--four-chips"]) == 0
"""


def test_four_chip_path_on_four_host_devices():
    """``--four-chips`` runs only the sharded path, on a 2x2 mesh of four
    host devices (the device count is fixed before jax starts, hence the
    child process)."""
    import os
    import subprocess

    root = str(_SCRIPT.parent)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([f"{root}/src", root]))
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIPS_CHILD.format(root=root)],
        env=env, capture_output=True, text=True, timeout=600)
    out = proc.stdout
    assert proc.returncode == 0, out + proc.stderr
    assert "FAIL" not in out, out
    for phase in ("ingest", "cold_solve", "blocked_update", "cached_solve"):
        assert f"phase {phase}:" in out, out
    for phase in ("phase1", "upload", "serve", "stream", "churn"):
        assert f"phase {phase}:" not in out, out
    assert json.loads(out.splitlines()[-1])["device"]["count"] == 4


def test_refuses_a_host_without_a_tpu(monkeypatch, capsys):
    smoke = _load(monkeypatch)
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""
