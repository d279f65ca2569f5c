"""What a CPU run can say about the chip entry points: ``chip_smoke.py``
refuses to report ``ok`` without a TPU, and the compile-cache helper
puts the cache where it says."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


_PRINT_CACHE_DIR = (
    "import jax; from byteps_tpu.common.config import enable_compile_cache; "
    "print(enable_compile_cache()); "
    "print(jax.config.jax_compilation_cache_dir)")


def _cache_dirs(cwd, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_over, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run([sys.executable, "-c", _PRINT_CACHE_DIR], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=300,
                       check=True)
    return r.stdout.split()


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    """Unset: ``<checkout>/.jax_cache`` whatever the working directory —
    the path is part of the cache key, so it must not move."""
    want = os.path.join(ROOT, ".jax_cache")
    assert _cache_dirs(str(tmp_path)) == [want, want]
    assert _cache_dirs(ROOT) == [want, want]


def test_compile_cache_env_is_left_alone(tmp_path):
    """Set: JAX reads the variable itself; the helper sets nothing."""
    d = str(tmp_path / "elsewhere")
    assert _cache_dirs(str(tmp_path), JAX_COMPILATION_CACHE_DIR=d) == [d, d]
