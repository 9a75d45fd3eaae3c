"""CLI regression tests for ``launch/serve.py --mode fusion``.

Every pool-serving flag combination is smoked in-process (argv patched, no
subprocess): the run must complete cleanly AND the solve-exactness it
reports — every tenant's served weights vs its cold ``core.fusion``
reference — must hold, because a serving CLI that exits 0 while serving
wrong weights is the worst kind of green. Shapes are tiny; this is a
correctness/flag-wiring gate, not a perf measurement.
"""
import re
import sys

import pytest

from repro.launch import serve

BASE = ["serve.py", "--mode", "fusion", "--dim", "24", "--tenants", "3",
        "--clients", "2", "--samples", "32", "--queries", "8",
        "--sharded-tenants", "0", "--auto-tenants", "0"]

COMBOS = {
    "dense_only": [],
    "sharded": ["--sharded-tenants", "1"],
    "mixed_all_three": ["--sharded-tenants", "1", "--auto-tenants", "1"],
    "stream_deltas": ["--stream-deltas", "6", "--coalesce-rank", "4",
                      "--flush-staleness", "0.05"],
    "max_warm": ["--max-warm", "1"],
    "everything": ["--sharded-tenants", "1", "--auto-tenants", "1",
                   "--stream-deltas", "6", "--coalesce-rank", "4",
                   "--flush-staleness", "0.05", "--max-warm", "2"],
}


def _run_cli(monkeypatch, capsys, extra):
    monkeypatch.setattr(sys, "argv", BASE + extra)
    serve.main()   # any exception/SystemExit fails the test = exit status
    return capsys.readouterr().out


@pytest.mark.parametrize("name", list(COMBOS))
def test_fusion_cli_combo(name, monkeypatch, capsys):
    out = _run_cli(monkeypatch, capsys, COMBOS[name])
    assert "[serve_fusion]" in out
    # Reported exactness: every max|dw| the run printed must be small.
    errs = [float(v) for v in re.findall(r"max\|dw\|=([0-9.eE+-]+)", out)]
    assert errs, f"no exactness report in output:\n{out}"
    assert all(e < 1e-3 for e in errs), out
    if "--stream-deltas" in extra_set(name):
        assert "0 left pending" in out, out          # flusher drained
        assert re.search(r"(\d+) background flushes", out), out
        assert int(re.search(r"(\d+) background flushes", out).group(1)) >= 1
    if "--sharded-tenants" in extra_set(name) and "1" in COMBOS[name]:
        assert "'sharded': 1" in out, out
        assert "meshes_built=1" in out, out
    else:
        assert "meshes_built=0" in out, out


def extra_set(name):
    return set(COMBOS[name])


def test_fusion_cli_reports_ledger(monkeypatch, capsys):
    from repro.fed import wire

    out = _run_cli(monkeypatch, capsys, [])
    m = re.search(r"ledger: (\d+) upload bytes \+ (\d+) streamed", out)
    assert m, out
    # 3 tenants x 2 clients; each upload is priced at its encoded Thm-4
    # frame length (fed.wire), each download at d fp32 floats, d=24.
    d = 24
    per_client = wire.stats_frame_nbytes(d, "f32") + d * 4
    assert int(m.group(1)) == 3 * 2 * per_client
    assert int(m.group(2)) == 0


def test_model_mode_still_requires_arch(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["serve.py", "--mode", "model"])
    with pytest.raises(SystemExit):
        serve.main()


@pytest.mark.parametrize("env_set", [True, False])
def test_compilation_cache_helper(monkeypatch, tmp_path, env_set):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the helper leaves JAX's config
    untouched (JAX reads the variable itself); unset, the cache goes to the
    fixed checkout path, the same on every run."""
    import pathlib

    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    env_dir = str(tmp_path / "env-cache")
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compilation_cache()
        if env_set:
            assert path == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        else:
            repo = pathlib.Path(__file__).resolve().parents[1]
            assert path == str(repo / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert compile_cache.enable_compilation_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
