"""utils/runtime.py: where compiled programs are cached. The helper flips
process-global JAX config, so each case runs in a subprocess. The sandbox
has no accelerator, so the accelerator cases fake the backend NAME — the
helper never touches a device. (On the CPU backend, i.e. everywhere in
this suite, it is a no-op, which is why entry-point mains can be called
in-process by other tests.)"""

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys, jax
from asyncrl_tpu.utils import runtime
if sys.argv[1] != "cpu":
    jax.default_backend = lambda: sys.argv[1]
before = jax.config.jax_compilation_cache_dir
returned = runtime.enable_compile_cache()
print(json.dumps({
    "before": before, "returned": returned,
    "after": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


def _probe(cache_env, backend="tpu"):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE, backend],
        env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_var_wins_and_no_other_directory_is_set(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    got = _probe(placed)
    # JAX read the variable itself; the helper changed no directory.
    assert got["before"] == got["after"] == got["returned"] == placed
    assert got["min_secs"] == 0.0


def test_default_is_the_fixed_in_checkout_directory():
    first, second = _probe(None), _probe(None)
    expected = os.path.join(_ROOT, ".jax_cache")
    assert first["before"] is None
    assert first["after"] == first["returned"] == expected
    # Fixed across processes: never built from a pid, a time or a temp
    # name — the directory is part of what makes a second run hit.
    assert second["returned"] == expected


def test_cpu_programs_are_not_cached():
    got = _probe(None, backend="cpu")
    assert got["returned"] is None and got["after"] is None
    assert got["min_secs"] != 0.0


def test_cache_entries_counts_programs(tmp_path):
    from asyncrl_tpu.utils import runtime

    assert runtime.cache_entries(str(tmp_path / "absent")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert runtime.cache_entries(str(tmp_path)) == 1
