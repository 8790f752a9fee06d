"""Process-level rules every entry point shares: which device a
measurement may run on, and where compiled programs are cached.

Both are decided once, before the first trace, in the one process that
will hold the chip — a TPU belongs to one process at a time, so nothing
here probes from a child.
"""

from __future__ import annotations

import os
import sys

FORCE_CPU_ENV = "ASYNCRL_FORCE_CPU"
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# <root>/asyncrl_tpu/utils/runtime.py -> <root>/.jax_cache. Fixed, never a
# temp name: the directory is part of what makes a second run hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for accelerator
    programs; returns the directory, or None where nothing was enabled.

    With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the directory itself
    and no other is set here; unset, the cache lives at the fixed
    in-checkout ``.jax_cache`` (git-ignored).

    The minimum-compile-time threshold drops from JAX's 1 s default to 0:
    the host path's programs build in under a second each (the
    ``pong_serve`` MLP inference step: 0.5-0.6 s for a v5e target), so at
    the default they would never be cached and every cold start would
    pay for all of them again.

    The cache key includes the ops' metadata (JAX's default strips it):
    ``jax.named_scope`` names and source lines live there, and a profile
    reads device time by scope. Without it a program that differs from a
    cached one only in its scopes loads the cached executable, and its
    trace carries the names of whichever commit filled the directory.

    A CPU backend is left alone: an XLA:CPU executable is specific to
    the CPU it was built for while the directory travels with the tree,
    and on jax 0.9.0 every CPU cache hit logs "Target machine feature
    +prefer-no-gather is not supported on the host machine ... could
    lead to execution errors such as SIGILL" even on the machine that
    built it.
    """
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def cache_entries(cache_dir: str) -> int:
    """Number of compiled programs in ``cache_dir`` (0 if it does not
    exist yet) — a second run of the same command should not add any."""
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0


def require_tpu(tool: str) -> str:
    """The device rule of every measuring entry point: run on the TPU or
    exit nonzero. CPU only when asked for explicitly (``ASYNCRL_FORCE_CPU=1``
    — tier-1 and the CPU smokes use it); the returned platform is then
    ``"cpu"`` and every metric the caller prints carries that label
    (``device_entry``). Never a silent switch."""
    import jax

    if os.environ.get(FORCE_CPU_ENV, "") not in ("", "0"):
        jax.config.update("jax_platforms", "cpu")
        print(f"{tool}: {FORCE_CPU_ENV} set; running on CPU", file=sys.stderr)
        return "cpu"
    try:
        platform = jax.devices()[0].platform
    except RuntimeError as e:
        print(f"{tool}: no TPU ({e}); refusing to measure", file=sys.stderr)
        sys.exit(4)
    if platform != "tpu":
        print(
            f"{tool}: no TPU (jax reports platform={platform!r}); refusing "
            f"to measure — set {FORCE_CPU_ENV}=1 for an explicitly "
            "labelled CPU run",
            file=sys.stderr,
        )
        sys.exit(4)
    return platform


def device_entry() -> dict:
    """Platform/device fields of the current JAX backend: the label on
    every record an entry point prints."""
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "device_kind": d.device_kind,
        "device_count": jax.device_count(),
    }
