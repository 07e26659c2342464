"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU or
without the repo beside it, and its phases pass at a tiny size in
interpret mode (the chip runs them at full width).  Also the compile
cache helper the entry points share."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(cwd, script):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return proc.returncode != 0 and '"ok"' not in last


def test_refuses_without_tpu():
    proc = _run(ROOT, SCRIPT)
    assert _no_result(proc), proc.stdout
    assert "no TPU found" in proc.stdout


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run(tmp_path, tmp_path / "chip_smoke.py")
    assert _no_result(proc), proc.stdout


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke at a tiny size: smoke config, small pool and requests;
    the decode-program check looks for Mosaic kernels, which interpret
    mode does not emit."""
    import repro.configs
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    monkeypatch.setattr(repro.configs, "get_config",
                        repro.configs.get_smoke_config)
    monkeypatch.setattr(cs, "N_SLOTS", 4)
    monkeypatch.setattr(cs, "MAX_LEN", 128)
    monkeypatch.setattr(cs, "PAGE", 8)
    monkeypatch.setattr(cs, "PROMPT_LENS", (8, 24, 40))
    monkeypatch.setattr(cs, "N_REQUESTS", 5)
    monkeypatch.setattr(cs, "MAX_NEW", 6)
    monkeypatch.setattr(cs, "decode_has_kernels", lambda eng: True)
    return cs


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_phases_pass_at_smoke_size(smoke, kind):
    smoke.check_logits(kind, 0, 2 * smoke.PAGE)
    clock = smoke.CompileClock()
    assert smoke.serve_phase(kind, 0, clock) \
        == smoke.N_REQUESTS * smoke.MAX_NEW


def test_logit_check_fails_on_a_wrong_cache(smoke, monkeypatch):
    """The comparison is not vacuous: a paged cache that drops the
    prefix page writes moves the logits past the tolerance."""
    import repro.serve.paged as paged
    monkeypatch.setattr(paged, "scatter_prefill_cache",
                        lambda cache, *a, **k: cache)
    with pytest.raises(AssertionError, match="differ from the reference"):
        smoke.check_logits("bf16", 0, 2 * smoke.PAGE)


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
