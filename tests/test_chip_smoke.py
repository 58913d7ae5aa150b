"""chip_smoke.py refuses to report a result when JAX finds no TPU."""
import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        assert '"ok": true' not in line
        if line.startswith("{"):
            assert not json.loads(line).get("ok")


_CACHE_DIR = (
    "import jax; from repro.runtime.compile_cache import CHECKOUT_CACHE, use_compile_cache; "
    "print(use_compile_cache(), jax.config.jax_compilation_cache_dir, CHECKOUT_CACHE, "
    "jax.config.jax_persistent_cache_min_compile_time_secs)"
)


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    """Entry points keep JAX's cache in JAX_COMPILATION_CACHE_DIR when it is
    set, else at the checkout's fixed .jax_cache/; either way every compile
    is kept."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    unset = subprocess.run([sys.executable, "-c", _CACHE_DIR], env=env,
                           capture_output=True, text=True, timeout=120)
    returned, configured, checkout, min_secs = unset.stdout.split()
    assert returned == configured == checkout and checkout.endswith(".jax_cache")
    assert float(min_secs) == 0
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    given = subprocess.run([sys.executable, "-c", _CACHE_DIR], env=env,
                           capture_output=True, text=True, timeout=120)
    returned, configured, _, _ = given.stdout.split()
    assert returned == configured == str(tmp_path)
