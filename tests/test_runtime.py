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

import pytest

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
    "metadata_in_key": jax.config.jax_compilation_cache_include_metadata_in_key,
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
    # scope names are part of a program's identity (a profile reads them)
    assert got["metadata_in_key"] is True


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


def test_require_tpu_runs_on_cpu_only_when_asked(monkeypatch, capsys):
    """The one device rule (utils/runtime.py): refuse without a TPU;
    ASYNCRL_FORCE_CPU=1 is the explicit opt-in, announced on stderr."""
    from asyncrl_tpu.utils import runtime

    monkeypatch.delenv("ASYNCRL_FORCE_CPU", raising=False)
    with pytest.raises(SystemExit) as e:
        runtime.require_tpu("tool")
    assert e.value.code == 4
    assert "tool: no TPU" in capsys.readouterr().err
    monkeypatch.setenv("ASYNCRL_FORCE_CPU", "1")
    assert runtime.require_tpu("tool") == "cpu"
    assert "running on CPU" in capsys.readouterr().err
    assert runtime.device_entry()["platform"] == "cpu"


_SCOPES_PROBE = """
import json, os, sys
import jax, jax.numpy as jnp
from asyncrl_tpu.utils import runtime
jax.default_backend = lambda: "tpu"
cache_dir = runtime.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

def make(scope):
    def scoped_probe(x):
        with jax.named_scope(scope):
            return jnp.tanh(x) * 3
    return jax.jit(scoped_probe)

texts = []
for scope in ("alpha", "beta"):
    f = make(scope)
    f(jnp.ones(4)).block_until_ready()
    texts.append(f.lower(jnp.ones(4)).compile().as_text())
print(json.dumps({
    "entries": sum(n.startswith("jit_scoped_probe-") and n.endswith("-cache")
                   for n in os.listdir(cache_dir)),
    "beta_names_beta": "beta/tanh" in texts[1],
}))
"""


def test_a_program_that_differs_only_in_its_scopes_is_not_a_cache_hit(tmp_path):
    """A profile reads device time by ``jax.named_scope``: an executable
    loaded from the cache must carry this source's names, not those of
    the commit that filled the directory."""
    proc = subprocess.run(
        [sys.executable, "-c", _SCOPES_PROBE],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=120, cwd=_ROOT,
    )
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"entries": 2, "beta_names_beta": True}
