"""chip_smoke.py off the chip, and the compile-cache helper.

The smoke proves the serving path on a TPU; here it must refuse the CPU
(before it builds a model) and its phases must run at the reduced
minicpm-2b shape, so a change to the engine that would break the chip run
shows up without one.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_refuses_cpu(tmp_path, alone):
    """On the CPU it exits non-zero within seconds and prints no result,
    in the checkout and in a directory holding only the script."""
    script = SMOKE
    if alone:
        script = tmp_path / SMOKE.name
        shutil.copy(SMOKE, script)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr
    assert time.perf_counter() - t0 < 60


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env

    monkeypatch.delenv(compile_cache.ENV_VAR)
    fixed = str(ROOT / ".jax_cache")
    assert compile_cache.cache_dir() == fixed
    try:
        assert compile_cache.enable_compile_cache() == fixed
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_phases_at_reduced_shape(monkeypatch):
    """Phases A-D at the reduced minicpm-2b shape, with the Pallas kernel
    interpreted (the chip run compiles it; interpret mode emits no TPU
    kernel, so that one check is the chip's)."""
    from repro import configs

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(smoke, "PALLAS", {"use_pallas": True,
                                          "interpret": True})
    logits = smoke.prefill_logits

    def interpreted(ex, prompts, kernel, **kw):
        out, hlo = logits(ex, prompts, kernel, **kw)
        return out, hlo + "tpu_custom_call" * bool(kernel.get("interpret"))

    monkeypatch.setattr(smoke, "prefill_logits", interpreted)
    smoke.one_chip(configs.get_config("minicpm-2b", reduced=True), seed=0)
