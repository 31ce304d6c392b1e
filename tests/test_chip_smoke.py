"""chip_smoke.py's no-fallback guard, and the one function that places
the persistent compile cache (utils.flops.enable_compile_cache)."""

import os
import subprocess
import sys

import jax

from paddle_tpu.utils import flops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu():
    """On the CPU the script exits non-zero at the first phase's device
    check — before any model is built — and never prints a result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env,
                       cwd=REPO)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    assert "not 'tpu'" in r.stdout
    # it stopped at the first phase: no later phase was started
    assert "[serve]" not in r.stdout and "[http]" not in r.stdout


def test_compile_cache_dir_from_the_environment_is_left_alone(
        monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax already uses it — return it
    and set nothing in code."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert flops.enable_compile_cache() == str(tmp_path / "c")
    assert calls == []
    assert not (tmp_path / "c").exists()   # nothing was created either


def test_compile_cache_defaults_to_the_fixed_checkout_path(monkeypatch):
    """Unset: the fixed <checkout>/.jax_cache (the path is part of the
    cache key, so it is never made from a temp name, pid or time)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert flops.enable_compile_cache() == want
    assert flops.enable_compile_cache() == want    # and it never moves
    assert calls == [("jax_compilation_cache_dir", want)] * 2
    assert os.path.isdir(want)
