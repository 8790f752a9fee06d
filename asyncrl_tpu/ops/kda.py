"""Kimi Delta Attention's state recurrence (gated delta rule with a decay
per key channel), in its two forms.

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``kda_step`` is the recurrence itself, one token: the rollout's form, all
float32 on the vector unit (the state is the traffic: 2 x dk x dv x 4 bytes
a head a token). ``kda_chunk`` is the learner's: the same function over a
whole fragment, ``chunk`` tokens at a time on the matrix unit, with one
state hand-over per chunk. Within a chunk (G the inclusive cumulative sum
of g from the chunk's start, ``~`` "no episode boundary between"):

    u_t = b_t (v_t - [t~0] S_0^T (k_t e^{G_t})
                   - sum_{i<t, i~t} u_i  k_t.(k_i e^{G_t-G_i}))
    o_t = [t~0] S_0^T (q_t e^{G_t}) + sum_{i<=t, i~t} u_i q_t.(k_i e^{G_t-G_i})
    S_C = [C~0] e^{G_C} S_0 + sum_{i~C} (k_i e^{G_C-G_i}) u_i^T

so ``(I + A) U = b (V - K~ S_0)`` with ``A`` strictly lower triangular. An
episode boundary cuts pairs by a mask on the pairwise terms; the cumulative
sums run on through it (nothing is set to -inf). Every exponent that is
ever formed is <= 0: pairs inside a 16-token sub-chunk take the exact
difference ``G_t - G_i`` per channel, pairs across sub-chunks factor it
through the sub-chunks' edges, so no decay rate overflows a float32.

Decays, cumulative sums, the state and every accumulation are float32;
``dtype`` is what the operands of the matrix products are cast to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from asyncrl_tpu.obs import introspect

SUB = 16  # sub-chunk: pairs inside it take exact per-channel differences
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def kda_step(S, q, k, v, g, beta):
    """One token. ``S`` [B, H, dk, dv] float32; ``q``, ``k``, ``g``
    [B, H, dk]; ``v`` [B, H, dv]; ``beta`` [B, H]. Returns ``(S, o)``."""
    introspect.count_kda_site("step")
    with jax.named_scope("kda_step"):
        S = S * jnp.exp(g)[..., None]
        u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
        S = S + k[..., None] * u[..., None, :]
        return S, jnp.sum(S * q[..., None], axis=-2)


def _mm(spec, a, b, dtype):
    """Matrix product with operands in ``dtype`` and a float32 result;
    float32 operands multiply at full precision."""
    return jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype),
        precision=HIGHEST if dtype == F32 else None,
        preferred_element_type=F32,
    )


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower triangular ``A`` [..., C, C]:
    ``A`` is nilpotent, so the inverse is the finite product
    ``(I - A)(I + A^2)(I + A^4)...`` -- log2(C) squarings on the matrix
    unit in place of C substitution steps."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=F32)
    inv, power = eye - A, A
    n = 2
    while n < C:
        power = jnp.matmul(power, power, precision=HIGHEST)
        inv = jnp.matmul(inv, eye + power, precision=HIGHEST)
        n *= 2
    return inv


def _pairwise(tk, k, G, g_first, dtype):
    """``P[s, t, i] = sum_d tk[s, t, d] k[i, d] exp(G[t, d] - G[i, d])`` for
    ``i <= t``, 0 above the diagonal. ``tk`` [..., S, C, dk] stacks the
    targets (k and q); ``k``, ``G`` [..., C, dk]; ``g_first`` is g itself
    (to step back from an inclusive sum to a sub-chunk's start)."""
    *lead, n_tgt, C, dk = tk.shape
    c = min(SUB, C)
    n = C // c
    sub = lambda x: x.reshape(*x.shape[:-2], n, c, dk)
    Gs, ks, tks = sub(G), sub(k), sub(tk)
    start = Gs[..., 0, :] - sub(g_first)[..., 0, :]  # [..., n, dk]
    end = Gs[..., -1, :]

    # inside a sub-chunk: the exact difference, masked before the exp, for
    # every pair at once: one reduce over dk, whose [c, c, dk] operand XLA
    # keeps from a chunk's forward to its backward (268 MB a block of envs)
    later = jnp.tril(jnp.ones((c, c), bool))[:, :, None]  # [t, i, 1]
    diff = jnp.where(later, Gs[..., :, None, :] - Gs[..., None, :, :], 0.0)
    src = ks[..., None, :, :] * jnp.where(later, jnp.exp(diff), 0.0)
    # [..., S, n, c(t), c(i)]
    diag = jnp.sum(tks[..., None, :] * src[..., None, :, :, :, :], axis=-1)
    if n == 1:
        return diag[..., 0, :, :]

    # across sub-chunks J < I: e^{G_t - start_I} e^{start_I - end_J} e^{end_J - G_i}
    before = jnp.tril(jnp.ones((n, n), bool), -1)[..., None]
    gap = start[..., :, None, :] - end[..., None, :, :]  # [..., I, J, dk]
    mid = jnp.where(before, jnp.exp(jnp.where(before, gap, 0.0)), 0.0)
    tgt = tks * jnp.exp(Gs - start[..., None, :])[..., None, :, :, :]
    src = ks * jnp.exp(end[..., None, :] - Gs)
    off = _mm(
        "...sItJd,...Jid->...sIJti",
        tgt[..., :, :, None, :] * mid[..., None, :, None, :, :], src, dtype,
    )
    blocks = off + diag[..., :, None, :, :] * jnp.eye(n, dtype=F32)[:, :, None, None]
    # [..., S, I, J, t, i] -> [..., S, C, C]
    return jnp.swapaxes(blocks, -3, -2).reshape(*lead, n_tgt, C, C)


def _chunk(S0, xs, dtype):
    """One chunk; leaves of ``xs`` are [B, H, C, ...] (``done`` [B, C])."""
    q, k, v, g, beta, done = xs
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    G = jnp.cumsum(g, axis=2)
    ends = jnp.cumsum(done.astype(jnp.int32), axis=1)
    seg = (ends - done.astype(jnp.int32))[:, None, :]  # boundaries strictly before t
    from_s0 = (seg == 0).astype(F32)[..., None]  # [B, 1, C, 1]
    same = seg[..., :, None] == seg[..., None, :]  # [B, 1, C(t), C(i)]
    last = ends[:, None, -1:]  # boundaries up to the chunk's end
    to_end = (seg == last).astype(F32)[..., None]
    s0_to_end = (last == 0).astype(F32)[..., None]

    P = _pairwise(jnp.stack([k, q], axis=2), k, G, g, dtype)
    P = jnp.where(same[:, :, None], P, 0.0)
    A = jnp.tril(P[:, :, 0], -1) * beta[..., None]
    inv = _unit_lower_inverse(A)
    k_in = k * jnp.exp(G)
    w = jnp.matmul(inv, beta[..., None] * k_in * from_s0, precision=HIGHEST)
    u0 = jnp.matmul(inv, beta[..., None] * v, precision=HIGHEST)

    u = u0 - _mm("bhck,bhkv->bhcv", w, S0, dtype)
    o = _mm("bhck,bhkv->bhcv", q * jnp.exp(G) * from_s0, S0, dtype)
    o = o + _mm("bhti,bhiv->bhtv", P[:, :, 1], u, dtype)
    k_out = k * jnp.exp(G[:, :, -1:] - G) * to_end
    S = S0 * jnp.exp(G[:, :, -1])[..., None] * s0_to_end
    S = S + _mm("bhck,bhcv->bhkv", k_out, u, dtype)
    return S, o


def kda_chunk(S0, q, k, v, g, beta, done, chunk: int = 64, dtype=F32):
    """A fragment. ``q``, ``k``, ``g`` [T, B, H, dk]; ``v`` [T, B, H, dv];
    ``beta`` [T, B, H]; ``done`` [T, B] (the state is zero for the token
    after a done one); ``S0`` [B, H, dk, dv]. Returns ``(S_T, o [T, B, H,
    dv])``; ``S_T`` is the state after token T-1 and before its reset.
    ``T`` need not divide by ``chunk``: the tail is padded with tokens that
    neither decay nor write."""
    introspect.count_kda_site("chunk")
    T = q.shape[0]
    C = min(chunk, -(-T // SUB) * SUB)
    assert C % min(SUB, C) == 0, f"chunk {chunk} is not a multiple of {SUB}"
    pad = -T % C

    def chunks(x):  # [T, B, (H), ...] -> [n, B, (H), C, ...]
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        x = x.reshape((T + pad) // C, C, *x.shape[1:])
        return jnp.moveaxis(x, 1, 2 if x.ndim == 3 else 3)

    # q, k, v enter the products as ``dtype`` operands: the scan holds them
    # (and their cotangents) in it; decays and beta stay float32
    xs = tuple(chunks(x.astype(dtype)) for x in (q, k, v)) + tuple(
        chunks(x.astype(F32)) for x in (g, beta)
    ) + (chunks(done),)

    with jax.named_scope("kda_chunk"):
        body = jax.checkpoint(functools.partial(_chunk, dtype=dtype))
        S, o = jax.lax.scan(body, S0.astype(F32), xs)
    # [n, B, H, C, dv] -> [T, B, H, dv]
    o = jnp.moveaxis(o, 3, 1).reshape(T + pad, *o.shape[1:3], o.shape[-1])
    return S, o[:T]
