"""Entry-point guards for running on a chip host: one process per chip
(no child process or re-exec when the backend is not the CPU) and a
compilation cache whose place is fixed or set from outside."""
import os
import subprocess
import sys

import jax
import pytest

from repro.errors import ConfigError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def one_chip_backend(monkeypatch):
    """This process sees one device of a non-CPU backend, and starting a
    child process or re-exec'ing fails the test."""
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def no_child(*a, **k):
        raise AssertionError("a child process was started")

    monkeypatch.setattr(os, "execvpe", no_child)
    monkeypatch.setattr(subprocess, "run", no_child)
    monkeypatch.setattr(subprocess, "Popen", no_child)


def test_launcher_refuses_missing_chips(one_chip_backend):
    from repro.launch.bfs import ensure_devices
    with pytest.raises(ConfigError, match="requested 4 devices.*tpu "
                                          "backend has 1"):
        ensure_devices(4, [])


def test_launcher_simulates_devices_only_on_cpu():
    """On the CPU backend the guard still asks for the simulated-device
    child, and enough devices need none."""
    from repro.distributed.bfs_dist import needs_simulated_devices
    have = len(jax.devices())
    assert not needs_simulated_devices(have)
    assert needs_simulated_devices(have + 1)


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    from repro import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def _default_cache_dir() -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = ("import jax\n"
            "from repro.compile_cache import use_compile_cache\n"
            "d = use_compile_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == d\n"
            "print(d)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    return out.stdout.strip()


def test_compile_cache_default_is_fixed_in_checkout():
    from repro.compile_cache import DEFAULT_DIR
    first, second = _default_cache_dir(), _default_cache_dir()
    assert first == second == DEFAULT_DIR
    assert first == os.path.join(REPO, ".jax_cache")
