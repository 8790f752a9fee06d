"""ctypes wrapper for the native C++ vectorized env pool (native/envpool.cc).

This is the framework's ALE-analogue: a C++ engine stepping hundreds of envs
per call behind a batched C ABI, feeding the Sebulba host path
(SURVEY.md §2.1, §7.2 M3). ctypes releases the GIL during ``envpool_step``,
so Python actor threads overlap env stepping with device inference.

The library auto-builds via ``make`` on first use (g++ is in the image;
SURVEY.md §7.0) into ``native/build/``, under a name keyed by what it was
built from — see :func:`_lib_path`.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_BUILD_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None


def _host_fingerprint() -> str:
    """What ``-march=native`` resolves against: the machine type and the
    CPU's feature flags (first ``flags`` line of /proc/cpuinfo; empty
    where that is unreadable)."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{platform.machine()} {flags}"


def _lib_path() -> str:
    """Library path keyed by a hash of the source, the Makefile (the
    flags) and the host CPU. The build is ``-march=native``, and a tree
    copied between machines carries ``native/build/`` along: an artifact
    from another source revision or another CPU must never be the one
    loaded, which an mtime comparison cannot guarantee."""
    h = hashlib.sha256()
    for name in ("envpool.cc", "Makefile"):
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    h.update(_host_fingerprint().encode())
    return os.path.join(
        _NATIVE_DIR, "build", f"libenvpool-{h.hexdigest()[:16]}.so"
    )


def _build(lib_path: str) -> None:
    """Compile to a private name, then rename: a concurrent process never
    loads a half-written library."""
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    proc = subprocess.run(
        [
            "make", "-C", _NATIVE_DIR,
            f"TARGET={os.path.relpath(tmp, _NATIVE_DIR)}",
        ],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"native env pool build failed (exit {proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}"
        )
    os.replace(tmp, lib_path)


def load_library() -> ctypes.CDLL:
    """Build (if needed) and load the shared library; cached per-process."""
    global _LIB
    if _LIB is not None:
        return _LIB
    lib_path = _lib_path()
    with _BUILD_LOCK:
        if _LIB is not None:
            return _LIB
        if not os.path.exists(lib_path):
            # lint: blocking-under-lock-ok(serializing the one-time compiler run IS this lock's job: concurrent first callers must block until the .so exists)
            _build(lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.envpool_create.restype = ctypes.c_void_p
        lib.envpool_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ]
        lib.envpool_reset.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.envpool_reseed.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.envpool_step.argtypes = [ctypes.c_void_p] + [ctypes.c_void_p] * 5
        lib.envpool_step_continuous.argtypes = (
            [ctypes.c_void_p] + [ctypes.c_void_p] * 5
        )
        lib.envpool_action_dim.argtypes = [ctypes.c_void_p]
        lib.envpool_action_dim.restype = ctypes.c_int
        lib.envpool_obs_dim.argtypes = [ctypes.c_void_p]
        lib.envpool_obs_dim.restype = ctypes.c_int
        lib.envpool_num_actions.argtypes = [ctypes.c_void_p]
        lib.envpool_num_actions.restype = ctypes.c_int
        lib.envpool_num_envs.argtypes = [ctypes.c_void_p]
        lib.envpool_num_envs.restype = ctypes.c_int
        lib.envpool_destroy.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return lib


# env ids the native engine implements, mapped from registry ids.
NATIVE_ENV_IDS = {
    "CartPole-v1": "CartPole-v1",
    "JaxPong-v0": "Pong",  # same rules as the JAX env (envs/pong.py)
    "JaxBreakout-v0": "Breakout",  # same rules as envs/breakout.py
    "JaxFreeway-v0": "Freeway",  # same rules as envs/minatari.py::Freeway
    # Continuous control: same dynamics as envs/pendulum.py (float
    # [B, 1] torque actions through envpool_step_continuous).
    "JaxPendulum-v0": "Pendulum",
}


class NativeEnvPool:
    """A batch of C++ envs stepped in one call.

    ``step`` takes int32 actions [B] (discrete pools) or float32 actions
    [B, action_dim] (continuous pools, ``self.continuous``) and returns
    ``(obs [B, D] f32, reward [B] f32, terminated [B] bool, truncated [B]
    bool)``; envs auto-reset (post-reset obs returned), matching the
    functional env contract (envs/core.py).
    """

    def __init__(
        self,
        env_id: str,
        num_envs: int,
        num_threads: int = 0,
        seed: int = 0,
    ):
        # Declared FIRST: close()/__del__ must be safe when __init__ dies
        # anywhere below (failed build, bad env id, envpool_create
        # failure) — a half-constructed pool has no handle to free.
        self._handle = None
        self._lib = None
        if env_id not in NATIVE_ENV_IDS:
            raise KeyError(
                f"no native implementation for {env_id!r}; "
                f"have {sorted(NATIVE_ENV_IDS)}"
            )
        if num_envs < 1:
            raise ValueError(f"num_envs must be >= 1, got {num_envs}")
        self._lib = load_library()
        if num_threads <= 0:
            # Threads pay off only for biggish batches.
            num_threads = min(8, max(1, num_envs // 64))
        self._seed = seed
        self._handle = self._lib.envpool_create(
            NATIVE_ENV_IDS[env_id].encode(), num_envs, num_threads, seed
        )
        if not self._handle:
            raise RuntimeError(f"envpool_create failed for {env_id!r}")
        self.num_envs = num_envs
        self.obs_dim = self._lib.envpool_obs_dim(self._handle)
        self.num_actions = self._lib.envpool_num_actions(self._handle)
        self.action_dim = self._lib.envpool_action_dim(self._handle)
        self.continuous = self.action_dim > 0
        # Reused output buffers: zero allocation in the hot loop.
        self._obs = np.empty((num_envs, self.obs_dim), np.float32)
        self._rew = np.empty((num_envs,), np.float32)
        self._term = np.empty((num_envs,), np.uint8)
        self._trunc = np.empty((num_envs,), np.uint8)
        # Chaos layer (utils/faults.py): one handle fetch; None when
        # unarmed (the hot step then pays a single identity check). The
        # owner (ActorThread) wires ``fault_stop`` so an injected stall
        # wakes when the thread is stopped/abandoned.
        from asyncrl_tpu.utils import faults

        self._fault_step = faults.site("pool.step")
        self.fault_stop = None

    def reset(self) -> np.ndarray:  # thread-entry: env-pool@actor
        """Re-seed (to the construction seed) and reset every env:
        ``reset()`` is deterministic no matter how far a reused pool's RNGs
        have advanced — evaluation pools cached across calls depend on
        this."""
        self._lib.envpool_reseed(self._handle, self._seed)
        self._lib.envpool_reset(self._handle, self._obs.ctypes.data)
        return self._obs.copy()

    def step(  # thread-entry: env-pool@actor
        self, actions: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Step all envs; returns fresh arrays safe to retain across calls
        (the C side writes into reused internal buffers; the copies here are
        noise next to the env-step cost, and ``step_into`` exists for
        zero-copy staging straight into a caller-owned fragment buffer)."""
        self.step_into(
            actions, self._obs, self._rew, self._term, self._trunc
        )
        return (
            self._obs.copy(),
            self._rew.copy(),
            self._term.astype(bool),
            self._trunc.astype(bool),
        )

    def step_into(
        self,
        actions: np.ndarray,
        obs_out: np.ndarray,
        rew_out: np.ndarray,
        term_out: np.ndarray,
        trunc_out: np.ndarray,
    ) -> None:
        """Zero-copy step: writes results into caller-owned C-contiguous
        arrays (obs [B, D] f32, rew [B] f32, term/trunc [B] u8). This is the
        Sebulba hot path — results land directly in the fragment staging
        buffer. Discrete pools take int32 [B] actions; continuous pools
        take float32 [B, action_dim]."""
        B = self.num_envs
        if self.continuous:
            actions = np.ascontiguousarray(actions, np.float32)
            if actions.shape != (B, self.action_dim):
                raise ValueError(
                    f"actions shape {actions.shape} != "
                    f"({B}, {self.action_dim})"
                )
        else:
            actions = np.ascontiguousarray(actions, np.int32)
            if actions.shape != (B,):
                raise ValueError(f"actions shape {actions.shape} != ({B},)")
        # The C side writes raw bytes through these pointers: every output
        # buffer must match the ABI's dtype/contiguity exactly or writes
        # corrupt the heap silently (no asserts: they vanish under -O).
        for name, arr, dtype, shape in (
            ("obs_out", obs_out, np.float32, (B, self.obs_dim)),
            ("rew_out", rew_out, np.float32, (B,)),
            ("term_out", term_out, np.uint8, (B,)),
            ("trunc_out", trunc_out, np.uint8, (B,)),
        ):
            if arr.dtype != dtype or arr.shape != shape or not arr.flags.c_contiguous:
                raise ValueError(
                    f"{name} must be C-contiguous {np.dtype(dtype).name}"
                    f"{shape}; got {arr.dtype}{arr.shape} "
                    f"contiguous={arr.flags.c_contiguous}"
                )
        step_fn = (
            self._lib.envpool_step_continuous
            if self.continuous
            else self._lib.envpool_step
        )
        step_fn(
            self._handle,
            actions.ctypes.data,
            obs_out.ctypes.data,
            rew_out.ctypes.data,
            term_out.ctypes.data,
            trunc_out.ctypes.data,
        )
        if self._fault_step is not None:
            # After the C call so crash/stall model a wedged engine and
            # corrupt poisons the full transition the caller will read —
            # the SAME field set the JAX pool's site damages, so the one
            # spec exercises the one recovery matrix on every backend.
            out = self._fault_step.fire(
                stop=self.fault_stop,
                payload=(obs_out, rew_out, term_out, trunc_out),
            )
            obs_out[...], rew_out[...], term_out[...], trunc_out[...] = out

    def disarm_faults(self) -> None:
        """Detach this pool from the chaos layer (evaluation pools step
        outside the supervised pipeline; see SebulbaTrainer.evaluate)."""
        self._fault_step = None

    @property
    def spec(self):
        """EnvSpec for the Sebulba trainer (continuous pools need the
        action_dim/continuous flags a bare obs_dim/num_actions fallback
        cannot express)."""
        from asyncrl_tpu.envs.core import EnvSpec

        if self.continuous:
            return EnvSpec(
                obs_shape=(self.obs_dim,),
                continuous=True,
                action_dim=self.action_dim,
            )
        return EnvSpec(
            obs_shape=(self.obs_dim,), num_actions=self.num_actions
        )

    def close(self) -> None:
        """Idempotent, and safe on a half-constructed pool: the handle is
        cleared BEFORE the destroy call, so even a re-entrant close (or a
        close racing __del__ at interpreter shutdown) can never double-free
        the C-side pool."""
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and self._lib is not None:
            self._lib.envpool_destroy(handle)

    def __del__(self):
        # No blanket try/except: close() is idempotent and handles every
        # partial-construction state, so an exception here is a REAL bug
        # (e.g. a double-free) that must not be masked.
        self.close()
