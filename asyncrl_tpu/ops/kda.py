"""Kimi Delta Attention's state recurrence (gated delta rule with a decay
per key channel), in its two forms.

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``kda_step`` is the recurrence itself, one token: the rollout's form, all
float32 on the vector unit (the state is the traffic: 2 x dk x dv x 4 bytes
a head a token). The delta rule has to reduce over a head's state
(``k^T D S``) before it can write it, and no XLA fusion reduces over a tile
and then broadcasts into the same tile without reading it twice: XLA's code
passes over the state three times a token. Where it can, ``kda_step``
is a Pallas kernel that holds a head's ``[dk, dv]`` tile in VMEM: one read,
one write, in place. Which path a call takes is read from what can be
observed, as in ``ops/max_pool.py``: the static shape when the call is
traced (``_kernel_fits``), the platform when the program is lowered
(``lax.platform_dependent``); the kernel's VJP is the plain form's.
``process_record()["kda_sites"]`` counts ``"step_kernel"`` and ``"step"``
(the plain form). ``fresh`` [B] marks envs whose episode ended on the token
before: their state is zero *as it is read*, so a reset never passes over
the state on its own (``models/kimi_linear.py SeqCore``).

``kda_chunk`` is the learner's: the same function over a
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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops.site import site_primitive

SUB = 16  # sub-chunk: pairs inside it take exact per-channel differences
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

# ------------------------------------------------------------- one token

_LANE, _SUBLANE = 128, 8
# One env's block of the state, in and out, double-buffered, must fit this;
# the v5e's VMEM is 128 MiB and the rest is the compiler's.
_VMEM_BLOCK_BUDGET = 64 * 1024 * 1024
_VMEM_HEADROOM = 16 * 1024 * 1024


def _plain_step(S, q, k, v, g, beta, fresh):
    if fresh is not None:
        S = jnp.where(fresh[:, None, None, None], 0.0, S)
    S = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return S, jnp.sum(S * q[..., None], axis=-2)


def _step_kernel(fresh_ref, beta_ref, s_ref, q_ref, k_ref, v_ref, g_ref,
                 s_out_ref, o_ref):
    """One env: s_ref (1, H, dk, dv) -> s_out_ref (the same buffer), o_ref
    (1, H, dv). A head's tile stays in registers from its load to its
    store. ``dk`` runs along the sublanes, so ``k``, ``q`` and the decay
    enter as columns: one transpose each of eight heads' ``[8, dk]`` rows
    (a loop over such groups, eight heads unrolled in it: the body is what
    every site traces). ``fresh`` and ``beta`` are scalars, from SMEM."""
    env = pl.program_id(0)
    fresh = fresh_ref[env] != 0

    def group(j, _):
        rows = pl.ds(pl.multiple_of(j * _SUBLANE, _SUBLANE), _SUBLANE)
        k_t, q_t = k_ref[0, rows].T, q_ref[0, rows].T  # [dk, 8]
        decay_t, v = jnp.exp(g_ref[0, rows]).T, v_ref[0, rows]
        out = []
        for i in range(_SUBLANE):
            h = j * _SUBLANE + i
            k = k_t[:, i:i + 1]  # [dk, 1]
            S = jnp.where(fresh, 0.0, s_ref[0, h]) * decay_t[:, i:i + 1]
            u = beta_ref[env, h] * (
                v[i:i + 1] - jnp.sum(S * k, axis=0, keepdims=True))
            S = S + k * u
            s_out_ref[0, h] = S
            out.append(jnp.sum(S * q_t[:, i:i + 1], axis=0, keepdims=True))
        o_ref[0, rows] = jnp.concatenate(out, axis=0)

    lax.fori_loop(0, s_ref.shape[1] // _SUBLANE, group, None)


def _kernel_fits(shape: tuple[int, ...], dtype) -> bool:
    """What the kernel asks of a static shape (the platform is asked when
    the program is lowered): float32, whole (8, 128) tiles (a head's state
    and an env's ``[H, dk]`` block, which is transposed), one env's block,
    in and out and double-buffered, inside the VMEM budget."""
    if len(shape) != 4 or jnp.dtype(dtype) != F32:
        return False
    B, H, dk, dv = shape
    return (B > 0 and H > 0 and H % _SUBLANE == 0
            and dk > 0 and dk % _LANE == 0 and dv > 0 and dv % _LANE == 0
            and 4 * 4 * H * dk * dv <= _VMEM_BLOCK_BUDGET)


def _kernel_step(S, q, k, v, g, beta, fresh, interpret=False):
    """``pallas_call`` over the envs, one a grid step (2 MB in and out at
    the published widths: the DMA's rate, 612 GB/s on a v5e, and blocks of
    2 and 4 envs read the same), the state updated in place
    (``input_output_aliases``: a second copy of the states is 0.5 GB the
    cell does not have). Outputs declare the inputs' varying mesh axes, as
    in ``ops/max_pool.py _call``."""
    B, H, dk, dv = S.shape
    vma = frozenset().union(
        *(jax.typeof(x).vma for x in (S, q, k, v, g, beta, fresh)))
    state = pl.BlockSpec((1, H, dk, dv), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM)

    def rows(d):
        return pl.BlockSpec((1, H, d), lambda i: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    scalars = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _step_kernel,
        name="kda_step",
        grid=(B,),
        in_specs=[scalars, scalars, state, rows(dk), rows(dk), rows(dv),
                  rows(dk)],
        out_specs=[state, rows(dv)],
        out_shape=[jax.ShapeDtypeStruct(S.shape, F32, vma=vma),
                   jax.ShapeDtypeStruct(v.shape, F32, vma=vma)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * 4 * H * dk * dv + _VMEM_HEADROOM,
        ),
        cost_estimate=pl.CostEstimate(
            flops=7 * B * H * dk * dv, transcendentals=B * H * dk,
            bytes_accessed=4 * B * H * (2 * dk * dv + 3 * dk + 2 * dv)),
        interpret=interpret,
    )(fresh.astype(jnp.int32), beta.astype(F32), S, q.astype(F32),
      k.astype(F32), v.astype(F32), g.astype(F32))


# Which form a site whose shape fits ended on is known where it is lowered.
_site_p = site_primitive("kda_step_site", introspect.count_kda_site)


@jax.custom_vjp
def _kernel_step_vjp(S, q, k, v, g, beta, fresh):
    """The kernel, with the plain form's VJP (the kernel has none of its
    own; the learner differentiates ``kda_chunk``, not this)."""
    return _kernel_step(S, q, k, v, g, beta, fresh)


def _kernel_step_fwd(*xs):
    return _kernel_step(*xs), xs


def _kernel_step_bwd(xs, cotangents):
    *operands, fresh = xs
    _, vjp = jax.vjp(lambda *o: _plain_step(*o, fresh), *operands)
    return (*vjp(cotangents), None)


_kernel_step_vjp.defvjp(_kernel_step_fwd, _kernel_step_bwd)


def kda_step(S, q, k, v, g, beta, fresh=None):
    """One token. ``S`` [B, H, dk, dv] float32; ``q``, ``k``, ``g``
    [B, H, dk]; ``v`` [B, H, dv]; ``beta`` [B, H]; ``fresh`` [B] bool (the
    envs that start from a zero state whatever ``S`` holds; None: none).
    Returns ``(S, o)``."""
    with jax.named_scope("kda_step"):
        if not _kernel_fits(S.shape, S.dtype):
            introspect.count_kda_site("step")
            return _plain_step(S, q, k, v, g, beta, fresh)
        if fresh is None:
            fresh = jnp.zeros(S.shape[:1], bool)
        return lax.platform_dependent(
            S, q, k, v, g, beta, fresh,
            tpu=lambda S, *xs: _kernel_step_vjp(
                _site_p.bind(S, path="step_kernel"), *xs),
            default=lambda S, *xs: _plain_step(
                _site_p.bind(S, path="step"), *xs),
        )


# ------------------------------------------------------------- a fragment


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
