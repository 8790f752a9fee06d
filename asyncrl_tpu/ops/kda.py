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

The pairs inside a sub-chunk are the one term with no matrix product in it:
the exponent is a pair's own per channel, so the sum over ``d`` has a ``[16,
16, dk]`` operand a sub-chunk, which XLA writes to HBM and reads again from
a chunk's forward to its backward (268 MB a block of envs). Where they can,
those lines (``_plain_pairs``) are a pair of Pallas kernels that hold a
sub-chunk's ``[16, dk]`` tiles in VMEM and write only the ``[16, 16]``
results, under a ``custom_vjp`` whose backward is the second kernel: ``E``
formed again on the chip, the residuals the inputs. Selected as the
one-token kernel is (``_pairs_fit`` when traced, the platform when
lowered), and counted: ``kda_sites`` holds ``"pair_kernel"`` and ``"pair"``
(the plain lines), once per site and program lowered; ``"chunk"`` counts
the calls of ``kda_chunk``.

Decays, cumulative sums, the state and every accumulation are float32;
``dtype`` is what the operands of the matrix products are cast to.
"""

from __future__ import annotations

import functools
import math

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
_site_p = site_primitive("kda_site", introspect.count_kda_site)


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


# Pairs inside a sub-chunk of ``c`` tokens: ``D[s, j, t, i] = sum_d tk[s, jc+t,
# d] k[jc+i, d] exp(G[jc+t, d] - G[jc+i, d])`` for ``i <= t``, 0 above the
# diagonal; ``tk`` [B, H, S, C, dk] stacks the targets, ``k``, ``G`` [B, H, C,
# dk]. The exponent is a pair's own per channel, so the operand of the sum
# over ``d`` is [c, c, dk] a sub-chunk.


def _on_or_below(c):
    """[c(t), c(i)]: source ``i`` is target ``t`` or before it."""
    return jnp.tril(jnp.ones((c, c), bool))


def _plain_pairs(tk, k, G, c):
    """The plain form, and the kernels' reference: the exact difference,
    masked before the exp, for every pair at once, one reduce over dk. XLA
    keeps a [c, c, dk] operand from a chunk's forward to its backward (268
    MB a block of envs)."""
    sub = lambda x: x.reshape(*x.shape[:-2], -1, c, x.shape[-1])
    Gs, ks, tks = sub(G), sub(k), sub(tk)
    later = _on_or_below(c)[:, :, None]  # [t, i, 1]
    diff = jnp.where(later, Gs[..., :, None, :] - Gs[..., None, :, :], 0.0)
    src = ks[..., None, :, :] * jnp.where(later, jnp.exp(diff), 0.0)
    # [..., S, n, c(t), c(i)]
    return jnp.sum(tks[..., None, :] * src[..., None, :, :, :, :], axis=-1)


def _source(i, p, t_of, G_p, g_row, k_row):
    """Source ``i`` of a sub-chunk against the targets ``t`` of its tile
    ``p`` (rows 8p..8p+7 on the sublanes; ``p >= i // 8``, the tiles above
    hold no target after ``i``): ``E[t, d] = exp(G[t, d] - G[i, d])`` and
    ``k[i, d] E[t, d]``. Only ``i``'s own tile has targets before it: the
    mask comes before the exp (they read 1, and the caller drops them)."""
    d = G_p - g_row
    if p == i // _SUBLANE:
        d = jnp.where(t_of >= i % _SUBLANE, d, 0.0)
    E = jnp.exp(d)
    return E, k_row * E


def _tiles(ref, h, base, c):
    """Head ``h``'s rows ``base..base+c`` of a (1, H, C, dk) ref, a tile each."""
    return [ref[0, h, pl.ds(base + p, _SUBLANE)] for p in range(0, c, _SUBLANE)]


def _pairs_fwd_kernel(g_ref, k_ref, *refs, c):
    """One env: g_ref, k_ref and the S target refs (1, H, C, dk) -> out_ref
    (1, H, c, n S c): row ``t``, lane ``(j, s, i)``, so a head's result is
    one dense tile (a last dimension of 16 is padded to 128 lanes in HBM).
    A sub-chunk's ``[8, dk]`` tiles stay in registers while its sources go
    by, ``E`` formed one source and tile at a time; the sum over ``d`` is a
    lane reduction whose column is selected into the result's lane. The
    loops over heads and sub-chunks are loops; the ``c`` sources are
    unrolled (a rolled loop waits out each source's chain of operations,
    six times slower). Pairs above the diagonal are the caller's to mask."""
    *tk_refs, out_ref = refs
    H, C, dk = g_ref.shape[1:]
    S, tiles = len(tk_refs), range(c // _SUBLANE)
    t_of = lax.broadcasted_iota(jnp.int32, (_SUBLANE, dk), 0)
    lane = lax.broadcasted_iota(jnp.int32, (_SUBLANE, out_ref.shape[3]), 1)

    def head(h, _):
        def sub_chunk(j, acc):
            base = pl.multiple_of(j * c, c)
            G, acc = _tiles(g_ref, h, base, c), list(acc)
            tks = [_tiles(r, h, base, c) for r in tk_refs]
            for i in range(c):
                row = pl.ds(base + i, 1)
                for p in tiles[i // _SUBLANE:]:
                    _, src = _source(
                        i, p, t_of, G[p], g_ref[0, h, row], k_ref[0, h, row])
                    for s in range(S):
                        col = jnp.sum(tks[s][p] * src, axis=-1, keepdims=True)
                        acc[p] = jnp.where(
                            lane == (j * S + s) * c + i, col, acc[p])
            return tuple(acc)

        acc = lax.fori_loop(
            0, C // c, sub_chunk, (jnp.zeros(lane.shape, F32),) * len(tiles))
        out_ref[0, h] = jnp.concatenate(acc, axis=0)

    lax.fori_loop(0, H, head, None)


def _pairs_bwd_kernel(g_ref, k_ref, *refs, c):
    """The forward's tiles and ``dd_ref`` (1, H, c, n S c), the cotangent in
    the forward's layout and already masked -> dg_ref, dk_ref and the S dtk
    refs (1, H, C, dk). ``E`` is formed again, one source and tile at a
    time, and never stored. With ``a_i[t, d] = sum_s dD[s, t, i] tk[s, t, d]``:

        dtk[s, t] = sum_i dD[s, t, i] k[i] E_i[t]    dk[i] = sum_t E_i[t] a_i[t]
        dG[t] = sum_i k[i] E_i[t] a_i[t] - k[t] dk[t]

    (dG's second term is the sum over the targets of source ``t``)."""
    S = (len(refs) - 3) // 2
    tk_refs, dd_ref = refs[:S], refs[S]
    dg_ref, dk_ref, *dtk_refs = refs[S + 1:]
    H, C, dk = g_ref.shape[1:]
    tiles = range(c // _SUBLANE)
    t_of = lax.broadcasted_iota(jnp.int32, (_SUBLANE, dk), 0)
    lane = lax.broadcasted_iota(jnp.int32, (_SUBLANE, dd_ref.shape[3]), 1)
    zero = jnp.zeros((_SUBLANE, dk), F32)

    def head(h, _):
        dD = [dd_ref[0, h, pl.ds(p * _SUBLANE, _SUBLANE)] for p in tiles]

        def sub_chunk(j, _):
            base = pl.multiple_of(j * c, c)
            G = _tiles(g_ref, h, base, c)
            tks = [_tiles(r, h, base, c) for r in tk_refs]
            dG, dK = [zero] * len(tiles), [zero] * len(tiles)
            dtks = [[zero] * len(tiles) for _ in range(S)]
            for i in range(c):
                row = pl.ds(base + i, 1)
                k_row, own = k_ref[0, h, row], i // _SUBLANE
                Ea_sum = zero
                for p in tiles[own:]:
                    E, src = _source(i, p, t_of, G[p], g_ref[0, h, row], k_row)
                    a = zero
                    for s in range(S):
                        col = jnp.sum(
                            jnp.where(lane == (j * S + s) * c + i, dD[p], 0.0),
                            axis=-1, keepdims=True)
                        a = a + col * tks[s][p]
                        dtks[s][p] = dtks[s][p] + col * src
                    Ea = E * a
                    dG[p] = dG[p] + k_row * Ea
                    Ea_sum = Ea_sum + Ea
                dK[own] = jnp.where(
                    t_of == i % _SUBLANE,
                    jnp.sum(Ea_sum, axis=0, keepdims=True), dK[own])
            k = _tiles(k_ref, h, base, c)
            for p in tiles:
                rows = pl.ds(base + p * _SUBLANE, _SUBLANE)
                dg_ref[0, h, rows] = dG[p] - k[p] * dK[p]
                dk_ref[0, h, rows] = dK[p]
                for ref, dtk in zip(dtk_refs, dtks):
                    ref[0, h, rows] = dtk[p]

        lax.fori_loop(0, C // c, sub_chunk, None)

    lax.fori_loop(0, H, head, None)


def _pairs_vmem(H, S, C, dk, c) -> int:
    """Double-buffered VMEM of the backward's grid step (the larger call)."""
    return 2 * 4 * H * (2 * (2 + S) * C * dk + c * max(S * C, _LANE))


def _pairs_fit(shape: tuple[int, ...], dtype, c: int) -> bool:
    """What the pair kernels ask of ``tk``'s static shape: float32, ``[B,
    H, S, C, dk]`` with whole (8, 128) tiles a sub-chunk, one env's blocks
    inside the VMEM budget."""
    if len(shape) != 5 or jnp.dtype(dtype) != F32:
        return False
    B, H, S, C, dk = shape
    return (min(shape) > 0 and dk % _LANE == 0 and c % _SUBLANE == 0
            and C % c == 0
            and _pairs_vmem(H, S, C, dk, c) <= _VMEM_BLOCK_BUDGET)


def _pairs_call(kernel, name, c, S, inputs, out_shapes, work, interpret):
    """``pallas_call`` over the envs, one a grid step (the forward reads 4
    MB at the published widths); ``work`` is the vector unit's operations
    per pair and channel. Outputs declare the inputs' varying mesh axes."""
    B, H, C, dk = inputs[0].shape
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))

    def spec(shape):
        return pl.BlockSpec((1, *shape[1:]), lambda b: (b, 0, 0, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(kernel, c=c),
        name=name,
        grid=(B,),
        in_specs=[spec(x.shape) for x in inputs],
        out_specs=[spec(shape) for shape in out_shapes],
        out_shape=[jax.ShapeDtypeStruct(shape, F32, vma=vma)
                   for shape in out_shapes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_pairs_vmem(H, S, C, dk, c) + _VMEM_HEADROOM,
        ),
        cost_estimate=pl.CostEstimate(
            flops=work * B * H * C * c * dk, transcendentals=B * H * C * c * dk,
            bytes_accessed=4 * sum(math.prod(x.shape) for x in inputs)
            + 4 * sum(map(math.prod, out_shapes))),
        interpret=interpret,
    )(*inputs)


def _packed(D):
    """``[B, H, S, n, t, i]`` <-> ``[B, H, t, n, S, i]``, its own inverse."""
    return jnp.swapaxes(D, 2, 4)


# jit: a layer's sites (the primal pass, two rematerialisations, the backward)
# and the layers share one trace and one lowering of each unrolled kernel
@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _kernel_pairs(tk, k, G, c, interpret=False):
    B, H, S, C, _ = tk.shape
    (out,) = _pairs_call(
        _pairs_fwd_kernel, "kda_pairs_fwd", c, S,
        [G, k, *(tk[:, :, s] for s in range(S))], [(B, H, c, S * C)],
        3 + 2 * S, interpret)
    D = _packed(out.reshape(B, H, c, C // c, S, c))
    return jnp.where(_on_or_below(c), D, 0.0)


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _kernel_pairs_bwd(tk, k, G, dD, c, interpret=False):
    B, H, S, C, _ = tk.shape
    dD = jnp.where(_on_or_below(c), dD, 0.0)
    dG, dk, *dtk = _pairs_call(
        _pairs_bwd_kernel, "kda_pairs_bwd", c, S,
        [G, k, *(tk[:, :, s] for s in range(S)),
         _packed(dD).reshape(B, H, c, S * C)], [G.shape] * (2 + S),
        7 + 6 * S, interpret)
    return jnp.stack(dtk, axis=2), dk, dG


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pairs(tk, k, G, c):
    """``_plain_pairs`` at widths the kernels take: on a TPU a kernel that
    holds a sub-chunk's tiles in VMEM and writes only the ``[S, c, c]``
    result, with a backward of its own whose residuals are the inputs."""
    return _pairs_fwd(tk, k, G, c)[0]


def _pairs_fwd(tk, k, G, c):
    D = lax.platform_dependent(
        tk, k, G,
        tpu=lambda tk, *xs: _kernel_pairs(
            _site_p.bind(tk, path="pair_kernel"), *xs, c),
        default=lambda tk, *xs: _plain_pairs(
            _site_p.bind(tk, path="pair"), *xs, c))
    return D, (tk, k, G)


def _pairs_bwd(c, residuals, dD):
    return lax.platform_dependent(
        *residuals, dD,
        tpu=lambda tk, *xs: _kernel_pairs_bwd(
            _site_p.bind(tk, path="pair_kernel"), *xs, c),
        default=lambda tk, k, G, dD: jax.vjp(
            functools.partial(_plain_pairs, c=c),
            _site_p.bind(tk, path="pair"), k, G)[1](dD))


_pairs.defvjp(_pairs_fwd, _pairs_bwd)


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

    # inside a sub-chunk: [..., S, n, c(t), c(i)]
    if _pairs_fit(tk.shape, tk.dtype, c):
        diag = _pairs(tk, k, G, c)
    else:
        introspect.count_kda_site("pair")
        diag = _plain_pairs(tk, k, G, c)
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
