"""Mamba-2's state-space recurrence (SSD: a scalar decay per head, the input
and read-out vectors ``B``, ``C`` shared by the heads), in its two forms.

    S_t = a_t S_{t-1} + (delta_t u_t) B_t^T     a_t = exp(log_a_t) <= 1
    y_t = S_t C_t

``S`` [B, H, P, N] float32 per env, ``u`` [H, P], ``B``, ``C`` [N] a token;
``D u`` and the gate are the caller's (``models/granite_h.py``).

``ssd_step`` is the recurrence itself, one token: the rollout's form, all
float32 on the vector unit. The state is its traffic (2 x H x P x N x 4
bytes an env a token): its read, its reset and its write sit under scope
``ssd_step``. ``fresh`` [B] marks envs whose episode ended on the token
before: their state is zero *as it is read*, so a reset never passes over
the state on its own and no select outside this scope writes it
(``models/seq_common.py SeqCore``). XLA may still move a state between HBM
and VMEM by asynchronous copies that run under other operations of the
token loop (the cell's step compiled for a v5e does so for four of its nine
layers, and would for a Pallas kernel's operand too), so the time under
``ssd_step`` need not hold the state's bytes.

``ssd_chunk`` is the learner's: the same function over a whole fragment,
``chunk`` tokens at a time on the matrix unit, one state hand-over a chunk.
Within a chunk, ``c_t`` the inclusive cumulative sum of ``log_a`` from the
chunk's start and ``t ~ s`` "no episode boundary between":

    y_t = sum_{s <= t, s ~ t} e^{c_t - c_s} (C_t . B_s) delta_s u_s
          + [t ~ 0] e^{c_t} S_0 C_t
    S_C = [C ~ 0] e^{c_C} S_0 + sum_{s ~ C} e^{c_C - c_s} delta_s u_s B_s^T

An episode boundary inside a chunk cuts pairs by a mask applied before the
exp (every exponent formed is <= 0 or -inf); the cumulative sums run on
through it. ``chunk_boundaries`` counts the boundaries a fragment's chunks
mask.

``process_record()["ssd_sites"]`` counts ``"step"`` and ``"chunk"`` sites,
once per site and program lowered (``ops/site.py``).

Decays, cumulative sums, the state and every accumulation are float32;
``dtype`` is what the operands of the chunked form's products are cast to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops.site import site_primitive

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

_site_p = site_primitive("ssd_site", introspect.count_ssd_site)


def ssd_step(S, u, delta, log_a, B, C, fresh=None):
    """One token. ``S`` [b, H, P, N] float32; ``u`` [b, H, P]; ``delta``,
    ``log_a`` [b, H]; ``B``, ``C`` [b, N]; ``fresh`` [b] bool (envs that
    start from a zero state whatever ``S`` holds; None: none). Returns
    ``(S, y [b, H, P])``."""
    with jax.named_scope("ssd_step"):
        S = _site_p.bind(S, path="step")
        if fresh is not None:
            S = jnp.where(fresh[:, None, None, None], 0.0, S)
        x = (delta[..., None] * u).astype(F32)
        S = (S * jnp.exp(log_a)[..., None, None]
             + x[..., None] * B.astype(F32)[:, None, None, :])
        return S, jnp.sum(S * C.astype(F32)[:, None, None, :], axis=-1)


def _mm(spec, a, b, dtype):
    """Matrix product with operands in ``dtype`` and a float32 result;
    float32 operands multiply at full precision."""
    return jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype),
        precision=HIGHEST if dtype == F32 else None,
        preferred_element_type=F32,
    )


def _chunk(S0, xs, dtype):
    """One chunk; ``x`` [C, b, H, P], ``log_a`` [C, b, H], ``B``, ``C`` [C,
    b, N], ``done`` [C, b]."""
    x, log_a, B, C, done = xs
    c = jnp.cumsum(log_a, axis=0)  # [C, b, H]
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0)
    seg = ends - done.astype(jnp.int32)  # boundaries strictly before t
    n = x.shape[0]
    pairs = (seg[:, None] == seg[None, :]) & jnp.tril(
        jnp.ones((n, n), bool))[..., None]  # [t, s, b]
    decay = jnp.exp(jnp.where(
        pairs[..., None], c[:, None] - c[None, :], -jnp.inf))  # [t, s, b, H]
    scores = _mm("tbn,sbn->tsb", C, B, dtype)
    y = _mm("tsbh,sbhp->tbhp", decay * scores[..., None], x, dtype)
    from_s0 = jnp.where(seg == 0, 1.0, 0.0)[..., None] * jnp.exp(c)  # [C, b, H]
    y = y + from_s0[..., None] * _mm("tbn,bhpn->tbhp", C, S0, dtype)
    last = ends[-1]  # boundaries up to the chunk's end [b]
    to_end = jnp.where(seg == last, 1.0, 0.0)[..., None] * jnp.exp(c[-1] - c)
    S = S0 * (jnp.where(last == 0, 1.0, 0.0)[:, None] * jnp.exp(c[-1]))[..., None, None]
    S = S + _mm("sbhp,sbn->bhpn", x * to_end[..., None], B, dtype)
    return S, y


def ssd_chunk(S0, u, delta, log_a, B, C, done, chunk: int = 256, dtype=F32):
    """A fragment. ``u`` [T, b, H, P]; ``delta``, ``log_a`` [T, b, H]; ``B``,
    ``C`` [T, b, N]; ``done`` [T, b] (the state is zero for the token after
    a done one); ``S0`` [b, H, P, N], zero where an episode starts. Returns
    ``(S_T, y [T, b, H, P])``; ``S_T`` is the state after token T-1, zero
    where that token ended its episode. ``T`` need not divide by ``chunk``:
    the tail is padded with tokens that neither decay nor write."""
    T = u.shape[0]
    n = min(chunk, T)
    pad = -T % n

    def chunks(a):  # [T, ...] -> [T / n, n, ...]
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(-1, n, *a.shape[1:])

    x = (delta[..., None] * u).astype(F32)
    xs = (chunks(x.astype(dtype)), chunks(log_a.astype(F32)),
          chunks(B.astype(dtype)), chunks(C.astype(dtype)), chunks(done))
    with jax.named_scope("ssd_chunk"):
        S0 = _site_p.bind(S0.astype(F32), path="chunk")
        body = jax.checkpoint(functools.partial(_chunk, dtype=dtype))
        S, y = lax.scan(body, S0, xs)
    return S, y.reshape(-1, *y.shape[2:])[:T]


def chunk_boundaries(done, chunk: int):
    """Episode boundaries ``ssd_chunk`` masks inside its chunks of a
    fragment ``done`` [T, b] (a done token that is not a chunk's last or the
    fragment's), and the chunks: (float32, float32)."""
    T, b = done.shape
    n = min(chunk, T)
    t = jnp.arange(T)
    inside = (t % n != n - 1) & (t != T - 1)
    return (jnp.sum(jnp.where(inside[:, None], done, False).astype(F32)),
            jnp.asarray(b * -(-T // n), F32))
