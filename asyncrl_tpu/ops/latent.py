"""Latent attention of one token over a cache of latent rows
(``models/mla.py step``), in its two forms.

A row of the cache [B, L, W] is a position's normed latent (its first ``dv``
lanes) and beside it the shared rope key: the whole row is the key of every
head's absorbed query [B, H, W], and its first ``dv`` lanes are the value.
Env ``b`` attends rows ``0 .. length[b]`` inclusive (the row written this
step is the last); scores are scaled by 1 / sqrt(``d``).

``_plain_latent`` is the plain lines: the scores over the cache's whole
capacity, the weighted rows over it again, under a mask.

Where it can, ``latent_step`` is a Pallas kernel that reads an env's rows up
to ``length[b]`` only, ``CHUNK`` rows at a time, and each row once: the
scores take the chunk's whole rows and the weighted values its first ``dv``
lanes from the same copy in VMEM. A running maximum and sum over an env's
chunks (float32), as ``ops/gqa.py``'s kernel keeps; rows of a last chunk
beyond ``length[b]`` are masked before the softmax and zeroed before the
second product, so what they hold never reaches the output.

Why a kernel of its own and not ``ops/gqa.py``'s body: that body copies
chunks by hand (``make_async_copy`` of a slice of the cache in HBM), and
Mosaic slices a copy's source in whole lane tiles only; a latent row is 576
lanes (512 + 64, padded to 640 on the chip), so no hand copy takes it whole
or takes the rope key's 64 lanes apart. A grid over (env, chunk) with the
row as the block's full width does: Mosaic's own pipeline copies the block.
The grid covers the capacity; the index map sends a step beyond an env's
last chunk to the next env's first (the copy that follows starts while the
last chunk is attended) and repeats it, and a block index that repeats is
not copied again, so a step beyond costs the grid's overhead and no copy.

Which path a call takes is read from what can be observed, as in
``ops/gqa.py``: the static shape when the call is traced (``_kernel_fits``),
the platform when the program is lowered (``lax.platform_dependent``), and
``length`` itself inside the kernel and its index map. The kernel's VJP is
the plain lines'. ``process_record()["mla_sites"]`` counts
``"step_kernel"`` and ``"step"`` (the plain lines), once per site and
program lowered.

Precision: the products' operands in the cache's dtype, float32
accumulation; scores, softmax and its sums float32; the probabilities cast
to the cache's dtype for the second product (the kernel casts them before
they are normalised, the plain lines after).
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

F32 = jnp.float32
_LANE = 128
# Rows a grid step, chosen on a v5e (PERF.md §6): ms a call at
# Moonlight's shape and lengths 0.163 at 256, 0.120 at 512, 0.112 at 1,024;
# at Kimi's 0.078, 0.071, 0.086. A step beyond an env's last chunk costs
# ~0.15 us.
CHUNK = 512
# The queries and the result, whole, and the chunks in flight must fit this;
# the v5e's VMEM is 128 MiB and the rest is the compiler's.
_VMEM_BLOCK_BUDGET = 64 * 1024 * 1024
_VMEM_HEADROOM = 16 * 1024 * 1024
# What a call costs is ``length``'s to say; XLA prefetches the weights of
# the products that follow under the call as far as it believes the call
# lasts (``ops/gqa.py _COST_CAPACITIES``). In capacities of the cache (a
# call in Moonlight's cell reads ~0.3 of one), with that cell's tokens/s
# (a v5e, PERF.md §6): 2: 3,719.3 and 1/2: 3,760.2 on one seed; 1/2: 3,760.1
# and 1/4: 3,765.7 on another. Weight prefetches in the rollout's loop body,
# compiled for a described v5e: none given 56, 1/8: 104, 1/4: 132, 1/2:
# 144, 1: 140, 2: 96, 4: 120 (the plain lines 112).
_COST_CAPACITIES = 0.5


def _plain_latent(q, rows, length, dv, d):
    """Both products over the cache's whole capacity under a mask, the
    models' masked softmax (``models/seq_common.py _softmax``)."""
    scores = jnp.einsum(
        "bhl,bpl->bhp", q, rows, preferred_element_type=F32) / math.sqrt(d)
    mask = (jnp.arange(rows.shape[1])[None, :] <= length[:, None])[:, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    e = jnp.where(mask, jnp.exp(scores), 0.0)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum(
        "bhp,bpl->bhl", probs.astype(rows.dtype), rows[..., :dv],
        preferred_element_type=F32,
    )


def _kernel(len_ref, q_ref, rows_ref, o_ref, m_ref, l_ref, acc_ref, *, d):
    """Grid (B, L / chunk), step (b, c): len_ref [B] (SMEM, prefetched),
    q_ref (B, H, W) whole, rows_ref (1, chunk, W) the block the index map
    chose, o_ref (B, H, dv) whole; m_ref, l_ref (H, 1) and acc_ref (H, dv)
    float32: env b's running maximum, sum and weighted rows."""
    b, c = pl.program_id(0), pl.program_id(1)
    chunk = rows_ref.shape[1]
    dv = o_ref.shape[-1]
    length = len_ref[b]
    last = lax.div(length, jnp.int32(chunk))

    @pl.when(c == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def attend(masked):
        rows = rows_ref[0]
        scores = lax.dot_general(
            q_ref[b], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=F32) / math.sqrt(d)  # [H, chunk]
        v = rows[:, :dv]
        if masked:  # an env's last chunk: rows beyond its length
            first = c * chunk
            lane = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            scores = jnp.where(first + lane <= length, scores, -jnp.inf)
            row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            v = jnp.where(first + row <= length, v, jnp.zeros_like(v))
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        shrink = jnp.exp(m - m_new)
        e = jnp.exp(scores - m_new)
        l_ref[...] = shrink * l_ref[...] + jnp.sum(e, axis=1, keepdims=True)
        acc_ref[...] = shrink * acc_ref[...] + jnp.dot(
            e.astype(rows.dtype), v, preferred_element_type=F32)
        m_ref[...] = m_new

    @pl.when(c < last)
    def _():
        attend(False)

    @pl.when(c == last)
    def _():
        attend(True)
        o_ref[b] = acc_ref[...] / l_ref[...]


def _rows_block(b, c, len_ref, *, chunk, envs):
    """Env ``b``'s chunk ``c`` up to its last; beyond it the next env's
    first (the last env's last), which the step after copies no more."""
    last = lax.div(len_ref[b], jnp.int32(chunk))
    beyond = c > last
    ahead = beyond & (b + 1 < envs)
    return (jnp.where(ahead, b + 1, b),
            jnp.where(ahead, 0, jnp.where(beyond, last, c)), 0)


def _vmem(q_shape, rows_shape, dv, dtype) -> int:
    """The call's VMEM: the queries and the result whole, the two blocks of
    rows in flight, the running sums (lanes padded to whole tiles)."""
    (B, H, W), lanes = q_shape, -(-q_shape[-1] // _LANE) * _LANE
    size = jnp.dtype(dtype).itemsize
    return (B * H * (lanes * size + dv * 4) + 2 * CHUNK * lanes * size
            + H * (dv + 2 * _LANE) * 4)


def _kernel_fits(q_shape, rows_shape, dv, dtype) -> bool:
    """What the kernel asks of the static shapes (the platform is asked when
    the program is lowered): rows in bfloat16 or float32, the queries as
    wide as the rows, values of whole lane tiles, a capacity of whole
    chunks, the queries and the result inside the VMEM budget."""
    if len(q_shape) != 3 or len(rows_shape) != 3:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    (B, H, W), (_, L, width) = q_shape, rows_shape
    return (min(B, H, L, dv) > 0 and rows_shape[0] == B and width == W
            and dv % _LANE == 0 and dv <= W and L % CHUNK == 0
            and _vmem(q_shape, rows_shape, dv, dtype) <= _VMEM_BLOCK_BUDGET)


def _kernel_step(q, rows, length, dv, d, interpret=False):
    """One ``pallas_call`` named ``mla_step``: the queries and the result
    whole in VMEM, the rows a block of ``CHUNK`` at a time. Outputs declare
    the inputs' varying mesh axes, as in ``ops/kda.py``."""
    B, H, W = q.shape
    L = rows.shape[1]
    dtype = rows.dtype
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q, rows, length)))
    n = int(_COST_CAPACITIES * L)
    return pl.pallas_call(
        functools.partial(_kernel, d=d),
        name="mla_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, L // CHUNK),
            in_specs=[
                pl.BlockSpec((B, H, W), lambda b, c, n: (0, 0, 0)),
                pl.BlockSpec((1, CHUNK, W), functools.partial(
                    _rows_block, chunk=CHUNK, envs=B)),
            ],
            out_specs=pl.BlockSpec((B, H, dv), lambda b, c, n: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), F32), pltpu.VMEM((H, 1), F32),
                            pltpu.VMEM((H, dv), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, dv), F32, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(q.shape, rows.shape, dv, dtype) + _VMEM_HEADROOM,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * (W + dv) * n, transcendentals=B * H * n,
            bytes_accessed=B * n * W * jnp.dtype(dtype).itemsize),
        interpret=interpret,
    )(length.astype(jnp.int32), q, rows)


# Which form a site whose shape fits ended on is known where it is lowered.
_site_p = site_primitive("mla_site", introspect.count_mla_site)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_step_vjp(q, rows, length, dv, d):
    """The kernel, with the plain lines' VJP (the learner differentiates the
    fragment form; only its bootstrap token comes by here)."""
    return _kernel_step(q, rows, length, dv, d)


def _kernel_step_fwd(q, rows, length, dv, d):
    return _kernel_step(q, rows, length, dv, d), (q, rows, length)


def _kernel_step_bwd(dv, d, xs, cotangent):
    q, rows, length = xs
    _, vjp = jax.vjp(lambda *o: _plain_latent(*o, length, dv, d), q, rows)
    return (*vjp(cotangent), None)


_kernel_step_vjp.defvjp(_kernel_step_fwd, _kernel_step_bwd)


def latent_step(q, rows, length, dv, d):
    """One token. ``q`` [B, H, W] the absorbed queries in the rows' dtype;
    ``rows`` [B, L, W] the latent cache, this token's row written;
    ``length`` [B] int32, the index of that row. Returns the weighted
    values [B, H, dv] float32. The caller's scope names the site."""
    if not _kernel_fits(q.shape, rows.shape, dv, rows.dtype):
        introspect.count_mla_site("step")
        return _plain_latent(q, rows, length, dv, d)
    return lax.platform_dependent(
        q, rows, length,
        tpu=lambda q, *xs: _kernel_step_vjp(
            _site_p.bind(q, path="step_kernel"), *xs, dv, d),
        default=lambda q, *xs: _plain_latent(
            _site_p.bind(q, path="step"), *xs, dv, d),
    )
