"""chip_smoke.py and the compile cache its entry points turn on.

The smoke itself runs only on a TPU; here it must refuse the CPU, and its
one-chip phase is rehearsed at smoke depth, where every check but the
compiled-kernel one (the CPU interprets the Pallas kernel) passes.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import cache

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_one_chip_phase_at_smoke_depth(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "FAILURES", [])
    chip_smoke.one_chip("cpu", smoke=True, batch=2, steps=3)
    assert chip_smoke.FAILURES == [
        "pallas step's compiled text holds tpu_custom_call"]


def test_compile_cache_defers_to_the_environment(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert cache.use_compile_cache() == "/elsewhere/cache"
    assert updates == []


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".jax_cache")
    assert cache.use_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_entry_points_leave_the_cache_off_at_import():
    """Only an entry point's main turns the cache on: a described-chip
    compile in a test must not be written to it."""
    import repro.launch.serve  # noqa: F401
    import repro.launch.train  # noqa: F401
    assert jax.config.jax_compilation_cache_dir in (
        None, os.environ.get("JAX_COMPILATION_CACHE_DIR"))
