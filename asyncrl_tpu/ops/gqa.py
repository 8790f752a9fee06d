"""Grouped-query attention of one token over a cache of rows, in its two
forms.

The cache holds a position's ``G`` key-value heads side by side in one row
(``keys``, ``values`` [B, L, G * dh]); env ``b`` attends rows ``0 ..
length[b]`` inclusive (the row written this step is the last); query head
``j`` of ``H`` reads key-value head ``j // (H / G)``.

``_plain_step`` is the plain lines: both products run over the cache's whole
rows and its whole capacity, batched over envs only (the cache is read as it
lies, once each), under a mask. A query is laid into its key-value head's
lanes of a row of zeros (``own``), and of the weighted values a head keeps
its own key-value head's lanes: ``H / G`` times ``G`` the flops of a product
a head group, on a step the cache's bytes bound.

Where it can, ``gqa_step`` is a Pallas kernel that leaves the cache in HBM
and copies an env's rows up to ``length[b]`` only, ``CHUNK`` rows at a time:
the rows beyond never leave HBM. One call walks the envs' chunks in order,
the copies ``_SLOTS - 1`` chunks ahead of the products (across envs too), a
running maximum and sum over an env's chunks (float32). The products keep
the ``own`` form: on the matrix unit a row of 512 lanes against 32 laid
queries costs what it costs to pass the row through once, and a head group
at a time would pass 64 lanes of it eight times. Rows of a last chunk beyond
``length[b]`` are masked before the softmax and zeroed before the second
product, so what they hold never reaches the output.

The kernel takes one optional operand more, a mask of chosen rows [B, L]
(``ops/dsa.py``'s selection; whole in VMEM, float32, a chunk's rows a
sublane): a row then counts if it is at or before ``length[b]`` AND chosen.
A chosen-out row up to ``length[b]`` holds finite data and its probability
is exactly 0, so only the scores are masked; a chunk in which no row counts
while the running maximum is still -inf leaves the running sums as they were
(the maximum is guarded, not the chunk skipped). Whether the mask is there
is read from the call: without it the kernel is, op for op, the kernel it
was.

Which path a call takes is read from what can be observed, as in
``ops/kda.py``: the static shape when the call is traced (``_kernel_fits``),
the platform when the program is lowered (``lax.platform_dependent``), and
``length`` itself inside the kernel. The kernel's VJP is the plain lines'.
``process_record()["gqa_sites"]`` counts ``"step_kernel"`` and ``"step"``
(the plain lines), once per site and program lowered.

Precision: the products' operands in the cache's dtype, float32
accumulation; scores, softmax and its sums float32; the probabilities cast
to the cache's dtype for the second product (the kernel casts them before
they are normalised, the plain lines after).
"""

from __future__ import annotations

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
# Rows a copy and a pair of products, and chunks in flight: chosen on the
# chip (PERF.md, PR 31).
CHUNK = 256
_SLOTS = 3
# The laid queries and the result, whole, and the chunks in flight must fit
# this; the v5e's VMEM is 128 MiB and the rest is the compiler's.
_VMEM_BLOCK_BUDGET = 64 * 1024 * 1024
_VMEM_HEADROOM = 16 * 1024 * 1024
# What a call costs is ``length``'s to say, and the compiler has to be told
# something: XLA prefetches the weights of the products that follow under
# the call only as far as it believes the call lasts. In capacities of the
# cache, with the rollout's ms an update in the cell (PERF.md, PR 31): none
# given, 36 prefetches a token where the plain lines' step has 88; 1/4:
# 496.7; 1/2: 488.6; 1, the call's bound: 479.5; 2: 472.0; 4: 472.0. Under
# a mask in Keye's cell, whose calls read a third of a capacity before 0.6
# GB of expert weights a step (PERF.md, PR 33): 1/4: 1,000.9; 1/2: 1,007.7;
# 1: 1,018.3; 2: 988.3; 4: 1,062.7. One value serves both.
_COST_CAPACITIES = 2


def _own(H, G):
    """[H, G]: query head ``j`` reads key-value head ``j // (H / G)``."""
    return (jnp.arange(H)[:, None] // (H // G) == jnp.arange(G)[None, :]).astype(F32)


def _laid(q, G, dtype):
    """[B, H, G * dh]: a query in its key-value head's lanes of a row of
    zeros."""
    B, H, dh = q.shape
    own = _own(H, G)[None, :, :, None]  # [1, H, G, 1]
    return (q[:, :, None, :] * own).reshape(B, H, G * dh).astype(dtype)


def _plain_step(q, keys, values, length):
    B, H, dh = q.shape
    G, dtype = keys.shape[-1] // dh, keys.dtype
    scores = jnp.einsum(
        "bhc,bpc->bhp", _laid(q, G, dtype), keys, preferred_element_type=F32,
    ) / math.sqrt(dh)
    mask = (jnp.arange(keys.shape[1])[None, :] <= length[:, None])[:, None, :]
    # the models' masked softmax (``models/seq_common.py _softmax``)
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    e = jnp.where(mask, jnp.exp(scores), 0.0)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhp,bpc->bhc", probs.astype(dtype), values,
        preferred_element_type=F32,
    )
    return jnp.sum(out.reshape(B, H, G, dh) * _own(H, G)[None, :, :, None], axis=2)


def _step_kernel(len_ref, q_ref, k_hbm, v_hbm, *refs):
    """All envs: len_ref [B] (SMEM), q_ref (B, H, W) the laid queries, k_hbm,
    v_hbm (B, L, W) left in HBM, and where the call passed one the mask of
    chosen rows, chosen_ref (B, L / chunk, chunk) float32 -> o_ref (B, H,
    dh). k_buf, v_buf (slots, chunk, W) take the copies; ``sems`` (2,
    slots). The loops over envs and over an env's chunks are loops; the
    copies run ahead by a pointer of their own, which crosses envs."""
    *chosen_ref, o_ref, k_buf, v_buf, sems = refs
    B, H, W = q_ref.shape
    slots, chunk = k_buf.shape[:2]
    dh = o_ref.shape[-1]
    per_group = H // (W // dh)
    dtype = k_buf.dtype

    def chunks_of(b):
        # lax.div, lax.rem: ``//`` and ``%`` lower through ``sign``, which
        # this Pallas cannot lower on a value that varies over a mesh axis
        return lax.div(len_ref[b], jnp.int32(chunk)) + 1

    def copies(b, c, slot):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        return (
            pltpu.make_async_copy(k_hbm.at[b, rows], k_buf.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(v_hbm.at[b, rows], v_buf.at[slot], sems.at[1, slot]),
        )

    def fetch(b, c, i):
        """Start chunk ``c`` of env ``b``, the ``i``-th of the call, and step
        the pointer."""
        @pl.when(b < B)
        def _():
            for copy in copies(b, c, lax.rem(i, jnp.int32(slots))):
                copy.start()  # lint: pallas-ok(waited by attend() when the products reach chunk i)

        last = c + 1 == chunks_of(jnp.minimum(b, B - 1))
        return jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1)

    def attend(b, c, carry, masked):
        m, l, acc, fb, fc, i = carry
        fb, fc = fetch(fb, fc, i + slots - 1)
        slot = lax.rem(i, jnp.int32(slots))
        for copy in copies(b, c, slot):
            copy.wait()
        scores = lax.dot_general(
            q_ref[b], k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=F32) / math.sqrt(dh)  # [H, chunk]
        if chosen_ref:  # a row that was not chosen holds finite data: no zeroing
            scores = jnp.where(chosen_ref[0][b, pl.ds(c, 1)] > 0, scores, -jnp.inf)
        v = v_buf[slot]
        if masked:  # an env's last chunk: rows beyond its length
            first = c * chunk
            lane = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
            scores = jnp.where(first + lane <= len_ref[b], scores, -jnp.inf)
            row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            v = jnp.where(first + row <= len_ref[b], v, jnp.zeros_like(v))
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        top = m_new
        if chosen_ref:
            # a chunk with no chosen row, and none before it: the maximum is
            # still -inf, and the chunk leaves m, l and acc as they were
            top = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        shrink = jnp.exp(m - top)
        e = jnp.exp(scores - top)
        l = shrink * l + jnp.sum(e, axis=1, keepdims=True)
        acc = shrink * acc + jnp.dot(
            e.astype(dtype), v, preferred_element_type=F32)
        return m_new, l, acc, fb, fc, i + 1

    head = lax.broadcasted_iota(jnp.int32, (H, W), 0)
    lane = lax.broadcasted_iota(jnp.int32, (H, W), 1)
    own = head // per_group == lane // dh

    def env(b, pointer):
        n = chunks_of(b)
        carry = (jnp.full((H, 1), -jnp.inf, F32), jnp.zeros((H, 1), F32),
                 jnp.zeros((H, W), F32), *pointer)
        carry = lax.fori_loop(
            0, n - 1, lambda c, carry: attend(b, c, carry, False), carry)
        _, l, acc, *pointer = attend(b, n - 1, carry, True)
        out = jnp.where(own, acc / l, 0.0)
        o_ref[b] = sum(out[:, g * dh:(g + 1) * dh] for g in range(W // dh))
        return tuple(pointer)

    pointer = (jnp.int32(0), jnp.int32(0))
    for i in range(slots - 1):
        pointer = fetch(*pointer, i)
    lax.fori_loop(0, B, env, (*pointer, jnp.int32(0)))


def _vmem(q_shape, rows_shape, dtype, masked=False) -> int:
    """The call's VMEM: the laid queries and the result whole (a result's
    ``dh`` lanes padded to a tile), the chunks in flight, and with a mask of
    chosen rows that too, whole (float32, a chunk's rows a sublane)."""
    (B, H, dh), (_, L, W) = q_shape, rows_shape
    size = jnp.dtype(dtype).itemsize
    return (B * H * (W * size + -(-dh // _LANE) * _LANE * 4)
            + 2 * _SLOTS * CHUNK * W * size
            + masked * B * -(-L // (8 * CHUNK)) * 8 * CHUNK * 4)


def _kernel_fits(q_shape, rows_shape, dtype, masked=False) -> bool:
    """What the kernel asks of the static shapes (the platform is asked when
    the program is lowered): rows of whole lane tiles in bfloat16 or float32,
    a capacity of whole chunks, the heads in whole groups, the queries, the
    result and the mask of chosen rows, if there is one, inside the VMEM
    budget."""
    if len(q_shape) != 3 or len(rows_shape) != 3:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    (B, H, dh), (_, L, W) = q_shape, rows_shape
    return (min(B, H, dh, L, W) > 0 and rows_shape[0] == B
            and W % _LANE == 0 and W % dh == 0 and H % (W // dh) == 0
            and L % CHUNK == 0
            and _vmem(q_shape, rows_shape, dtype, masked) <= _VMEM_BLOCK_BUDGET)


def _kernel_step(q, keys, values, length, chosen=None, interpret=False):
    """One ``pallas_call`` and no grid: the laid queries (4 MB at the
    published widths) and the result whole in VMEM, the cache in HBM
    (``pl.ANY``: no operand of its size is copied or laid out again).
    ``chosen`` [B, L], where given, is the mask of the rows that count (of
    those up to ``length``): one more operand, whole in VMEM, and the
    kernel's one more predicate; without it the call is what it was.
    Outputs declare the inputs' varying mesh axes, as in ``ops/kda.py``."""
    B, H, dh = q.shape
    L, W = keys.shape[1:]
    dtype = keys.dtype
    mask = () if chosen is None else (
        chosen.astype(F32).reshape(B, L // CHUNK, CHUNK),)
    vma = frozenset().union(
        *(jax.typeof(x).vma for x in (q, keys, values, length, *mask)))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = _COST_CAPACITIES * L
    return pl.pallas_call(
        _step_kernel,
        name="gqa_step",
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), whole, hbm, hbm,
                  *(whole for _ in mask)],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), F32, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((_SLOTS, CHUNK, W), dtype),
            pltpu.VMEM((_SLOTS, CHUNK, W), dtype),
            pltpu.SemaphoreType.DMA((2, _SLOTS)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(
                _vmem(q.shape, keys.shape, dtype, bool(mask)) + _VMEM_HEADROOM),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * H * W * rows, transcendentals=B * H * rows,
            bytes_accessed=2 * B * rows * W * jnp.dtype(dtype).itemsize),
        interpret=interpret,
    )(length.astype(jnp.int32), _laid(q, W // dh, dtype), keys, values, *mask)


# Which form a site whose shape fits ended on is known where it is lowered.
_site_p = site_primitive("gqa_site", introspect.count_gqa_site)


@jax.custom_vjp
def _kernel_step_vjp(q, keys, values, length):
    """The kernel, with the plain lines' VJP (the learner differentiates the
    fragment form; only its bootstrap token comes by here)."""
    return _kernel_step(q, keys, values, length)


def _kernel_step_fwd(*xs):
    return _kernel_step(*xs), xs


def _kernel_step_bwd(xs, cotangent):
    *operands, length = xs
    _, vjp = jax.vjp(lambda *o: _plain_step(*o, length), *operands)
    return (*vjp(cotangent), None)


_kernel_step_vjp.defvjp(_kernel_step_fwd, _kernel_step_bwd)


def gqa_step(q, keys, values, length):
    """One token. ``q`` [B, H, dh] float32, normed and rotated; ``keys``,
    ``values`` [B, L, G * dh], this token's row written; ``length`` [B]
    int32, the index of that row. Returns the heads' weighted values [B, H,
    dh] float32."""
    with jax.named_scope("gqa_step"):
        if not _kernel_fits(q.shape, keys.shape, keys.dtype):
            introspect.count_gqa_site("step")
            return _plain_step(q, keys, values, length)
        return lax.platform_dependent(
            q, keys, values, length,
            tpu=lambda q, *xs: _kernel_step_vjp(
                _site_p.bind(q, path="step_kernel"), *xs),
            default=lambda q, *xs: _plain_step(
                _site_p.bind(q, path="step"), *xs),
        )
