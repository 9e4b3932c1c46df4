"""The entry points' contract with the device: the chip smoke test
refuses a host without a TPU, the compile cache goes where it is told,
the benchmark driver reports failed panels, and the Pallas fill kernel
is refused where it cannot lower."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from benchmarks.run import run_panels
from repro import compile_cache
from repro.core.interface import JaxTarget

ROOT = Path(__file__).resolve().parent.parent


def test_chip_smoke_refuses_cpu_before_any_workload():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "phase" not in out.stdout and '"ok"' not in out.stdout


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got


def test_compile_cache_env_dir_is_left_alone(monkeypatch, tmp_path,
                                             restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_run_panels_keeps_going_and_names_failures(capsys):
    ran = []

    class Panel:
        def __init__(self, name, fail=False):
            self.name, self.fail = name, fail

        def run(self, quick=False):
            ran.append((self.name, quick))
            if self.fail:
                raise RuntimeError("boom")

    panels = [("a", Panel("a")), ("b", Panel("b", fail=True)),
              ("c", Panel("c"))]
    assert run_panels(panels, quick=True) == ["b"]
    assert ran == [("a", True), ("b", True), ("c", True)]
    assert "# b FAILED: RuntimeError: boom" in capsys.readouterr().out


def test_pallas_fetch_kernel_refused_off_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="u64"):
        JaxTarget(1, 1 << 21, fetch_kernel="pallas")
