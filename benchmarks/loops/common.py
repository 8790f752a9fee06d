"""What the measuring loops share: the profiler around a window, the
benchmark's own trace annotations, and the comparisons behind ``correct``."""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import sys
import time


class Phases:
    """Where set-up goes: seconds per phase, one line on stderr."""

    def __init__(self, t_process: float):
        self.last = t_process
        self.seconds: dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = round(now - self.last, 3)
        self.last = now

    def report(self) -> None:
        print(f"benchmarks: set-up phases (s): {self.seconds}", file=sys.stderr)


class Counters:
    """What every loop counts around its window: programs added to the
    compile cache by this run, programs asked of the backend inside the
    window, and the chip's peak memory."""

    def __init__(self, dev: dict):
        from benchmarks import device

        self.cache_dir = dev.get("cache_dir")
        self.cache0 = device.cache_entries(self.cache_dir)
        self.compiles = device.CompileLog()

    def read(self, t0: float, t1: float) -> dict:
        from benchmarks import device

        return {
            "compile_cache_added":
                device.cache_entries(self.cache_dir) - self.cache0,
            "compiles_in_window": self.compiles.between(t0, t1),
            "memory_peak_bytes": device.memory_peak_bytes(),
            # every program asked of the backend since before ``make_agent``:
            # the program's own record keeps its newest 16,384 events only
            "backend_compile_stamps": list(self.compiles.stamps),
        }


class Profiler:
    """``jax.profiler`` around part of a window; ``load`` reduces the
    ``.xplane.pb`` it wrote (kept under the git-ignored output directory,
    and replaced by the next traced run of the cell)."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")
        self.running = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.running = True

    def stop(self) -> None:
        import jax

        if self.running:
            jax.profiler.stop_trace()
            self.running = False

    def load(self):
        from benchmarks import xplane

        files = glob.glob(
            os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True
        )
        return xplane.load_trace(files[0]) if files else None


def annotate(name: str, on: bool):
    """The benchmark's own span in the profiler's trace (host side)."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def drawn_actor(env, num_envs: int, n_dev: int, episode_seed):
    """The actor state ``learner.init_state(episode_seed)`` builds over
    ``n_dev`` devices, without a policy's carry: the env batch's states,
    their observations and the key chains every later episode is drawn from
    (``envs/token_task.py`` draws a length when an episode starts, from the
    env's own key, never from an action or a parameter). Traceable in the
    seed, so ``benchmarks/episode_draw.py`` maps it over candidates."""
    import jax

    from asyncrl_tpu.learn.learner import derive_init_keys
    from asyncrl_tpu.rollout.anakin import actor_init

    _, akey = derive_init_keys(jax.random.PRNGKey(episode_seed))
    per_device = jax.vmap(
        lambda key: actor_init(env, num_envs // n_dev, key, model=None)
    )(jax.random.split(akey, n_dev))
    return jax.tree.map(
        lambda a: a.reshape(num_envs, *a.shape[2:]), per_device)


def with_episode_seed(actor, env, n_dev: int, episode_seed: int):
    """``actor`` with its env states, observations and key chains replaced
    by ``drawn_actor``'s, placed as the old leaves were. The policy's carry
    (gigabytes of empty caches in Keye's cell) stays the one ``make_agent``
    built. A mix that states an ``episode_seed`` meets the same episode
    lengths at the same steps whatever ``--seed`` the parameters have."""
    import jax

    drawn = jax.jit(
        lambda: drawn_actor(env, actor.obs.shape[0], n_dev, episode_seed))()
    placed = lambda new, old: jax.tree.map(
        lambda a, b: jax.device_put(a, b.sharding), new, old)
    return actor.replace(
        env_state=placed(drawn.env_state, actor.env_state),
        obs=placed(drawn.obs, actor.obs),
        keys=placed(drawn.keys, actor.keys),
    )


def param_delta(a, b) -> float:
    import jax
    import jax.numpy as jnp

    return float(sum(
        jnp.sum(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    ))


def check_replicas(state, n_dev: int) -> list[str]:
    """Across chips: the env batch is sharded over all of them, and every
    replica of a replicated parameter holds one value (un-reduced gradients
    do not crash, each chip just trains its own copy)."""
    import jax
    import numpy as np

    reasons = []
    obs = state.actor.obs
    if len(obs.sharding.device_set) != n_dev:
        reasons.append(
            f"env batch on {len(obs.sharding.device_set)} of {n_dev} devices"
        )
    for leaf in jax.tree.leaves(state.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if len(shards) != n_dev or not all(
            np.array_equal(shards[0], s) for s in shards[1:]
        ):
            reasons.append("param replicas differ across devices")
            break
    return reasons


def loss_tolerance(cfg) -> float:
    """Relative tolerance between the program's loss and the plain float32
    reference's, relative to the reference's magnitude. ``precision="f32"``:
    1e-4, rounding and summation order only, so a bfloat16 forward (which
    moves the result by 1e-3 and more) fails. ``bf16_matmul``: the configuration states bfloat16 products;
    the first update's loss then read 1.2e-3 and 1.4e-3 from the reference
    on the v5e (PR 22), so 1e-2, which is still far below what a wrong
    discount, clip, sign or layer gives (tens of percent)."""
    return 1e-4 if cfg.precision == "f32" else 1e-2


def reference_forward(cfg):
    from benchmarks.reference import plain

    return plain.FORWARDS[cfg.torso]


def reference_impala_loss(cfg, config_doc, params, fragment) -> float:
    """The plain reference's loss of ``fragment`` under ``params``, on one
    device, in float32."""
    import jax

    from benchmarks.reference import plain

    one = jax.local_devices()[0]
    params = jax.device_put(jax.device_get(params), one)
    fragment = jax.device_put(fragment, one)
    chunk = int(config_doc.get("reference_chunk", 1024))
    loss = jax.jit(
        lambda p, f: plain.impala_loss(
            reference_forward(cfg), p, f, gamma=cfg.gamma,
            value_coef=cfg.value_coef, entropy_coef=cfg.entropy_coef,
            rho_clip=cfg.vtrace_rho_clip, c_clip=cfg.vtrace_c_clip,
            chunk=chunk,
        )
    )(params, fragment)
    return float(loss)
