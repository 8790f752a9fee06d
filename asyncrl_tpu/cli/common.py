"""Shared CLI plumbing: preset/override resolution, the cpu_async platform
guard and the compile cache, used by every entry point (train / suite /
play / launch) so fixes cannot drift between them."""

from __future__ import annotations


def resolve_config(
    preset: str, overrides: list[str], steps: int | None = None
):
    """Preset + ``key=value`` overrides + optional --steps, resolved."""
    from asyncrl_tpu.configs import presets
    from asyncrl_tpu.utils.config import override

    cfg = override(presets.get(preset), overrides)
    if steps is not None:
        cfg = cfg.replace(total_env_steps=steps)
    return cfg


def prepare_runtime(cfg) -> None:
    """Process set-up every entry point runs before anything traces.

    The cpu_async parity backend is CPU-only by contract: restrict the
    platform list BEFORE any backend initializes, so JAX's global init
    never touches an attached accelerator (jax initializes ALL registered
    platforms on first device query). Then the persistent compile cache
    (utils/runtime.py)."""
    from asyncrl_tpu.utils import runtime

    if cfg.backend == "cpu_async":
        import jax

        jax.config.update("jax_platforms", "cpu")
    runtime.enable_compile_cache()
