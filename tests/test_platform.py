"""Process setup: where the persistent compile cache goes, and the chip
smoke's refusal to run without a TPU.

``setup_platform`` keeps a ``JAX_COMPILATION_CACHE_DIR`` it is given and
otherwise points JAX at one fixed directory inside the checkout, so every
run of an entry point finds the programs the last run compiled.
"""
import os
import pathlib
import subprocess
import sys
import warnings

import pytest

from repro.launch.platform import DEFAULT_CACHE_DIR, setup_platform

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def clean_env(monkeypatch):
    """No cache or precision setting in the environment; whatever
    setup_platform sets is undone after the test."""
    for var in ("JAX_COMPILATION_CACHE_DIR", "JAX_DEFAULT_MATMUL_PRECISION",
                "REPRO_PLATFORM", "REPRO_HOST_DEVICES"):
        monkeypatch.delenv(var, raising=False)
    with warnings.catch_warnings():
        # jax is already imported in the test process
        warnings.simplefilter("ignore", RuntimeWarning)
        yield


def test_compile_cache_dir_from_environment(clean_env, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    applied = setup_platform()
    assert applied["compilation_cache_dir"] == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


@pytest.mark.parametrize("calls", [1, 2])
def test_compile_cache_dir_default_is_fixed_in_checkout(clean_env, calls):
    for _ in range(calls):
        applied = setup_platform()
    assert applied["compilation_cache_dir"] == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
    assert os.environ["JAX_DEFAULT_MATMUL_PRECISION"] == "highest"


def test_compile_cache_dir_reaches_jax(clean_env):
    """A fresh process that calls setup_platform before importing JAX
    hands JAX the fixed directory."""
    code = ("from repro.launch.platform import setup_platform\n"
            "setup_platform()\n"
            "import jax\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_default_matmul_precision)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    assert out == [str(DEFAULT_CACHE_DIR), "highest"]


def test_chip_smoke_refuses_cpu():
    """With only a CPU, chip_smoke.py exits non-zero, names what it found
    and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "needs a TPU; JAX found cpu" in run.stderr
