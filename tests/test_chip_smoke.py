"""chip_smoke.py and the chip-only entry points, as far as a chipless
sandbox can hold them: the rehearsal runs and labels every line, the real
script refuses to run (and prints no result) without a TPU, the on-chip
kernel suite errors instead of skipping, and the compile cache is placed
from outside when the environment says where."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=600, **env):
    full = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=full, capture_output=True,
                          text=True, timeout=timeout)


def test_rehearsal_runs_and_labels_every_line(tmp_path):
    cache = tmp_path / "cache"
    default = os.path.join(REPO, ".jax_cache")
    default_before = sorted(os.listdir(default)) if os.path.isdir(default) else None
    # min compile time 0: every program is cached, however fast this machine compiles it
    proc = _run(["chip_smoke.py", "--rehearsal"], JAX_COMPILATION_CACHE_DIR=str(cache),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.splitlines()
    assert lines and all(ln.startswith("rehearsal: ") for ln in lines), lines
    result = json.loads(lines[-1].removeprefix("rehearsal: "))
    assert result == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    # a rehearsal prints no time, rate or utilisation under any name
    assert not any(re.search(r"\d s$|/s\b|MFU|utili[sz]ation", ln) for ln in lines), lines
    # the cache went where the environment said, and nowhere else
    assert any(cache.iterdir())
    assert f"compile cache: {cache} (JAX_COMPILATION_CACHE_DIR" in proc.stdout
    assert (sorted(os.listdir(default)) if os.path.isdir(default) else None) == default_before


def test_without_a_tpu_the_smoke_fails_and_prints_no_result():
    proc = _run(["chip_smoke.py"], timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr and "cpu" in proc.stderr
    # the rehearsal flag alone is not enough: it needs the explicit CPU pin
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearsal"], cwd=REPO,
                          env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""


def test_tests_tpu_without_a_tpu_is_an_error_not_a_skip():
    proc = _run(["-m", "pytest", "tests_tpu", "-q", "--co", "-p", "no:cacheprovider"], timeout=120)
    assert proc.returncode == 4, proc.stdout[-2000:]  # pytest usage error
    assert "needs a TPU" in proc.stderr
    assert "skipped" not in proc.stdout


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # nothing set in code
        # an executable is cached under its metadata too: its op_names are what a trace is read by
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed)


def test_requested_tpu_accelerator_raises_without_one(monkeypatch):
    from deepspeed_tpu.accelerator import real_accelerator

    monkeypatch.setenv("DS_ACCELERATOR", "tpu")
    monkeypatch.setattr(real_accelerator, "ds_accelerator", None)
    with pytest.raises(RuntimeError):
        real_accelerator.get_accelerator().device_count()
