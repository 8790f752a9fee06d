"""A routed-expert layer that is told which experts it holds.

The router scores all ``num_experts`` (by sigmoid with a correction bias, or
by a softmax over all of them: the model's choice, ``route``'s ``score``) and
chooses ``top_k`` of them per token, as the whole model does; this chip computes the part of the result
that its own experts give and adds nothing for the rest (on one chip the
layer runs without its exchange). No token is dropped and there is no
capacity factor. A static shape that can never overflow is every held
expert on every token (``dense``: a decode step's few tokens are computed
so, the weights bound it). Over a fragment's tokens the work has to follow
the assignments routed here, and the routing's density, known when the call
is traced, says how:

- sparse routing (8 of 256: a held expert's mean load is N/32): each
  expert's rows are gathered into a buffer of its own of eight times the
  mean load (``gathered``; a router is not balanced, least of all a fresh
  one: the fullest held expert of that cell is sent four times the mean),
  and a block that sends a held expert more than that is computed densely
  instead (``lax.cond`` on the observed load): the same result at four
  times the cost;
- dense routing (4 of 32: the mean load is N/8, so eight times it is every
  token and those buffers are the dense side): ONE buffer over all held
  experts (``grouped``), the block's local assignments sorted by expert into
  it, each expert's rows padded to whole tiles, and a loop over the tiles in
  use with a product per tile with the weights of the tile's expert: the
  work follows the assignments routed here, not the buffer's size. Its
  static size bounds the block's TOTAL local assignments (twice the expected
  ``N k held / E``, never more than the worst case ``N min(k, held)``), so
  an expert's imbalance costs nothing, and only a collective preference for
  the held experts overflows it: that block is computed densely
  (``lax.cond``), the same result.

A decode step's few tokens leave many held experts unchosen (16 tokens
choosing 8 of 128 experts leave 36% of a chip's 16 idle, on average), and
an unchosen expert's part is an exact zero: the dense side reads its
weights for nothing. Where that expected idle share, (1 - k/E)^N, is
``IDLE_SHARE_MIN`` or more (``skips_idle``: the rollout's 16 tokens of
Keye's and Moonlight's cells; not Kimi's 64, nor LFM2's 128, nor any block
of a fragment), a TPU computes only the chosen ones in the kernel ``moe_chosen``
(``"dense_skip"``), the same sum; every other platform keeps the dense
lines. The kernel's VJP is the dense lines'.

``process_record()["moe_sites"]`` counts, per site and program lowered,
which side a call was built with.
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
HIGHEST = jax.lax.Precision.HIGHEST
_LANE = 128
# Rows of a tile of the grouped side. A tile's products read its expert's
# weights once: at 512 rows they are bound by the MXU, not by those bytes
# (2 x 512 flop a weight byte at bfloat16 against the v5e's 240), and the
# padding to whole tiles is half a tile an expert.
TILE = 512
# A decode step computes only the held experts some token chose where the
# expected share of those no token chose is this or more: Keye's 0.356 and
# Moonlight's 0.207 are in; Kimi's 0.131 is out, since at its shape (64
# tokens) the kernel with no expert idle is slower than XLA's dense lines
# (PERF.md §6), and so is LFM2's ~4e-8.
IDLE_SHARE_MIN = 0.15
# Columns of F a copy of the kernel takes (of the gate, the up and the down
# projection at once): chosen on a v5e (PERF.md §6).
F_TILE = 256
# The rows, the result and the copies in flight must fit this; the v5e's
# VMEM is 128 MiB and the rest is the compiler's.
_VMEM_BLOCK_BUDGET = 64 * 1024 * 1024
_VMEM_HEADROOM = 16 * 1024 * 1024

_site_p = site_primitive("moe_site", introspect.count_moe_site)


def route(x, router_kernel, router_bias, top_k: int, scale: float,
          norm_eps: float = 0.0, score: str = "sigmoid"):
    """``x`` [N, D] -> (expert ids [N, k], weights [N, k]), in float32.
    ``score="sigmoid"``: sigmoid scores, the top k of score + correction
    bias (a buffer: no gradient reaches it; ``None``: no bias).
    ``score="softmax"``: a softmax over all the experts, the top k of it.
    Either way the weights are the chosen scores renormalised over their
    sum (``norm_eps`` added to it where the model's own code does)."""
    with jax.named_scope("moe_router"):
        logits = jnp.matmul(
            x.astype(F32), router_kernel.astype(F32), precision=HIGHEST
        )
        if score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        elif score == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError(f"unknown router score {score!r}")
        biased = scores if router_bias is None else (
            scores + jax.lax.stop_gradient(router_bias.astype(F32)))
        _, ids = jax.lax.top_k(biased, top_k)
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
        total = jnp.sum(chosen, axis=-1, keepdims=True)
        if norm_eps:
            total = total + norm_eps
        weights = scale * chosen / total
        return ids, weights


def _expert_act(x, gate, up, dtype):
    """``silu(gate x) * up x`` -> [E, n, F] float32; the weights carry a
    leading expert axis ([E, D, F]); ``x`` is [E, n, D], or [n, D] where
    every expert sees the same rows."""
    def mm(w):
        return jnp.einsum(
            "enk,ekf->enf" if x.ndim == 3 else "nk,ekf->enf",
            x.astype(dtype), w, preferred_element_type=F32,
        )

    return jax.nn.silu(mm(gate)) * mm(up)


def _down(act, down, dtype):
    """A product an expert: [E, n, F] x [E, F, D] -> [E, n, D] float32."""
    return jnp.einsum(
        "enf,efd->end", act.astype(dtype), down, preferred_element_type=F32)


def _dense_lines(x, tw, gate, up, down, dtype):
    """Every held expert on every row of ``x`` [n, D], each part times the
    rows' weights ``tw`` [n, E] (0 where a row did not choose the expert):
    a product an expert, then the experts' parts added one after the other
    in held order. As ONE product with two contracted axes ("enf,efd->nd")
    XLA tiles the sum by what else the program holds in VMEM, so a rollout
    inside the step and the same rollout alone summed in another order and
    sampled other tokens (PERF.md §6)."""
    act = _expert_act(x, gate, up, dtype) * tw.T[..., None]
    parts = _down(act, down, dtype)
    return functools.reduce(jnp.add, list(parts))


def held_token_weights(ids, weights, held):
    """[N, E_held]: the weight each held expert has for each token (0 where
    the token did not choose it)."""
    hit = ids[:, :, None] == jnp.asarray(held, ids.dtype)[None, None, :]
    return jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)


# ------------------------------------------------------------ grouped side


def _mm(a, b, dtype):
    return jnp.matmul(a.astype(dtype), b.astype(dtype), preferred_element_type=F32)


def _index(weights, e):
    return tuple(
        jax.lax.dynamic_index_in_dim(w, e, keepdims=False) for w in weights
    )


def _varying_as(a, like):
    """``a`` varying over the mesh axes ``like`` varies over (inside a
    ``shard_map``; the identity outside one). JAX casts a replicated operand
    of a product with a sharded one by itself; a scan's carry and a
    ``custom_vjp``'s operands have to be cast by hand."""
    axes = tuple(jax.typeof(like).vma - jax.typeof(a).vma)
    return jax.lax.pcast(a, axes, to="varying") if axes else a


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def tile_experts(xt, tile_expert, used, gate, up, down, dtype):
    """A SwiGLU per tile with the weights of the tile's expert: ``xt``
    [tiles, rows, D], ``tile_expert`` [tiles] int32, ``used`` the leading
    tiles that hold a row, weights [E, D, F] / [E, F, D] in ``dtype`` ->
    [tiles, rows, D] float32, zero past ``used``. A loop over the used
    tiles alone (the work follows the assignments, not the buffer's size)
    that indexes the weights: no per-tile copy of them is formed, in this
    pass or in the backward one (below: three float32 accumulators
    [E, D, F] in the carry, one expert's slice of each updated a tile)."""
    def one(i, y):
        x, (g, u, d) = xt[i], _index((gate, up, down), tile_expert[i])
        out = _mm(jax.nn.silu(_mm(x, g, dtype)) * _mm(x, u, dtype), d, dtype)
        return jax.lax.dynamic_update_index_in_dim(y, out, i, 0)

    return jax.lax.fori_loop(
        0, used, one, _varying_as(jnp.zeros(xt.shape, F32), xt))


def _tile_experts_fwd(xt, tile_expert, used, gate, up, down, dtype):
    return tile_experts(xt, tile_expert, used, gate, up, down, dtype), (
        xt, tile_expert, used, gate, up, down)


def _tile_experts_bwd(dtype, residuals, dy):
    xt, tile_expert, used, gate, up, down = residuals

    def one(i, carry):
        acc, dxt = carry
        x, e = xt[i], tile_expert[i]
        g, u, d = _index((gate, up, down), e)
        a, b = _mm(x, g, dtype), _mm(x, u, dtype)
        sig = jax.nn.sigmoid(a)
        s = a * sig
        dh = _mm(dy[i], d.T, dtype)
        da = dh * b * (sig + s * (1.0 - sig))
        db = dh * s
        dx = _mm(da, g.T, dtype) + _mm(db, u.T, dtype)
        steps = (_mm(x.T, da, dtype), _mm(x.T, db, dtype), _mm((s * b).T, dy[i], dtype))
        acc = tuple(
            jax.lax.dynamic_update_index_in_dim(
                total, jax.lax.dynamic_index_in_dim(total, e, keepdims=False) + step,
                e, 0)
            for total, step in zip(acc, steps)
        )
        return acc, jax.lax.dynamic_update_index_in_dim(
            dxt, dx.astype(dxt.dtype), i, 0)

    zeros = lambda shape, dtype: _varying_as(jnp.zeros(shape, dtype), dy)
    acc, dxt = jax.lax.fori_loop(0, used, one, (
        tuple(zeros(w.shape, F32) for w in (gate, up, down)),
        zeros(xt.shape, xt.dtype),
    ))
    return (dxt, None, None, *(
        total.astype(w.dtype) for total, w in zip(acc, (gate, up, down))))


tile_experts.defvjp(_tile_experts_fwd, _tile_experts_bwd)


def _picked(rows, index):
    """``rows[index]``, zeros where ``index`` points past the last row."""
    return jnp.take(rows, index, axis=0, mode="fill", fill_value=0)


# Tokens into the buffer's rows and back. Every assignment has a row of its
# own (``dest`` [N, k]: the row of token n's j-th assignment, past the last
# row if that expert is not held) and every row at most one token
# (``source`` [rows]: its token, N if it holds none), so the transpose of
# either gather is the other index's gather. Left to autodiff it is a
# scatter-add of whole rows, which the TPU runs a row at a time.


@jax.custom_vjp
def _to_rows(x, source, dest):
    """``x`` [N, D] -> the buffer [rows, D]."""
    return _picked(x, source)


def _to_rows_bwd(residuals, g):
    dest = residuals
    return sum(_picked(g, dest[:, j]) for j in range(dest.shape[1])), None, None


_to_rows.defvjp(lambda x, source, dest: (_picked(x, source), dest), _to_rows_bwd)


@jax.custom_vjp
def _from_rows(y, weights, source, dest):
    """The buffer's results [rows, D] -> sum over a token's assignments of
    ``weights`` [N, k] times its row: [N, D]."""
    return sum(_picked(y, dest[:, j]) * weights[:, j, None]
               for j in range(dest.shape[1]))


def _from_rows_bwd(residuals, g):
    y, weights, source, dest = residuals
    by_row = jnp.zeros((y.shape[0],), weights.dtype).at[dest.reshape(-1)].set(
        weights.reshape(-1), mode="drop")
    dweights = jnp.stack(
        [jnp.sum(_picked(y, dest[:, j]) * g, axis=-1) for j in range(dest.shape[1])],
        axis=1)
    return _picked(g, source) * by_row[:, None], dweights, None, None


_from_rows.defvjp(
    lambda y, weights, source, dest: (
        _from_rows(y, weights, source, dest), (y, weights, source, dest)),
    _from_rows_bwd)


def grouped_rows(n_tokens: int, top_k: int, n_held: int, num_experts: int,
                 tile: int) -> int:
    """Rows of the grouped side's buffer: twice the local assignments a
    balanced router sends a block (never more than the worst case), in
    whole tiles, and a tile an expert for the padding to whole tiles."""
    expected = n_tokens * top_k * n_held / num_experts
    worst = n_tokens * min(top_k, n_held)
    return (math.ceil(min(2 * expected, worst) / tile) + n_held) * tile


def _grouped(x, ids, weights, held, starts, ends, rows, tile, gate, up, down,
             dtype):
    """sum over held experts of ``w E(x)`` through one buffer of ``rows``
    rows, expert ``e``'s assignments in rows ``starts[e]`` on and its
    padding to whole tiles before ``ends[e]``."""
    n_tokens, width = x.shape
    n_held = len(held)
    # where each assignment goes: its expert's first row + its rank among
    # the expert's assignments (token order); ``rows`` (dropped on the way
    # in, zeros on the way back) if the expert is not held
    hit = (ids[:, :, None] == jnp.asarray(held, ids.dtype)[None, None, :])
    hit = hit.reshape(-1, n_held)
    rank = jnp.cumsum(hit.astype(jnp.int32), axis=0) - 1
    dest = jnp.sum(jnp.where(hit, rank + starts[None, :], 0), axis=1)
    dest = jnp.where(jnp.any(hit, axis=1), dest, rows).reshape(ids.shape)
    token = jnp.broadcast_to(jnp.arange(n_tokens)[:, None], ids.shape)
    source = jnp.full((rows,), n_tokens, jnp.int32).at[dest.reshape(-1)].set(
        token.reshape(-1), mode="drop")
    xg = _to_rows(x, source, dest)  # [rows, D]
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(rows // tile) * tile, side="right"),
        n_held - 1,
    ).astype(jnp.int32)
    y = tile_experts(
        xg.reshape(-1, tile, width), tile_expert, ends[-1] // tile,
        *(_varying_as(w, xg) for w in (gate, up, down)), dtype
    ).reshape(rows, width)
    return _from_rows(y, weights, source, dest)


# ------------------------------------------------- a decode step's chosen experts


def idle_share(n_tokens: int, top_k: int, num_experts: int) -> float:
    """The expected share of held experts that no token of a call chose:
    each of ``n_tokens`` tokens chooses ``top_k`` of ``num_experts``."""
    return (1.0 - top_k / num_experts) ** n_tokens


def skips_idle(n_tokens: int, top_k: int, num_experts: int) -> bool:
    """Whether a decode step of ``n_tokens`` computes only the held experts
    some token chose (``_chosen``): where the expected idle share is
    ``IDLE_SHARE_MIN`` or more."""
    return idle_share(n_tokens, top_k, num_experts) >= IDLE_SHARE_MIN


def _chosen_order(tw):
    """The held experts some token chose (a weight not 0 in ``tw`` [n, E])
    first, in held order, then the rest: (ids [E] int32, their count)."""
    chosen = jnp.any(tw != 0, axis=0)
    return (jnp.argsort(~chosen, stable=True).astype(jnp.int32),
            jnp.sum(chosen).astype(jnp.int32))


def _chosen_kernel(ids_ref, count_ref, x_ref, w_ref, stacked_hbm, o_ref,
                   buf, acc_ref, sem):
    """ids_ref [E] (SMEM) the chosen experts first, count_ref [1] their
    count; x_ref [n, D] and w_ref [E, n, 1] (the rows' weights an expert)
    whole in VMEM; stacked_hbm [E, 3, D, F] an expert's gate, up and down
    (transposed) in HBM, one copy a chosen expert's F tile of the three, the
    next tile's copy in flight while this one is computed; o_ref [n, D]
    float32; acc_ref [n, D] float32 an expert's part over its F tiles."""
    tile = buf.shape[-1]
    tiles = stacked_hbm.shape[-1] // tile
    total = count_ref[0] * tiles

    def copy(j, slot):
        cols = pl.ds(pl.multiple_of((j % tiles) * tile, tile), tile)
        return pltpu.make_async_copy(
            stacked_hbm.at[ids_ref[j // tiles], :, :, cols], buf.at[slot], sem.at[slot])

    o_ref[...] = jnp.zeros(o_ref.shape, F32)

    @pl.when(total > 0)
    def _():
        copy(0, 0).start()

    def step(j, carry):
        slot, f = j % 2, j % tiles

        @pl.when(j + 1 < total)
        def _():
            copy(j + 1, 1 - slot).start()

        copy(j, slot).wait()

        @pl.when(f == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        x = x_ref[...]
        a = jnp.dot(x, buf[slot, 0], preferred_element_type=F32)
        b = jnp.dot(x, buf[slot, 1], preferred_element_type=F32)
        h = (jax.nn.silu(a) * b * w_ref[ids_ref[j // tiles]]).astype(x.dtype)
        acc_ref[...] += lax.dot_general(
            h, buf[slot, 2], (((1,), (1,)), ((), ())), preferred_element_type=F32)

        @pl.when(f == tiles - 1)
        def _():
            o_ref[...] += acc_ref[...]

        return carry

    lax.fori_loop(0, total, step, 0)


def _f_tile(F: int) -> int:
    """Columns of an expert's F a copy takes: F where it is ``F_TILE`` or
    less, else the largest divisor of F in whole multiples of 128 up to it."""
    if F <= F_TILE:
        return F
    return max((t for t in range(_LANE, F_TILE + 1, _LANE) if F % t == 0), default=F)


def _kernel_vmem(n, D, F, dtype) -> int:
    """The call's VMEM: the rows, their weights an expert (lanes padded to a
    tile), two slots of the three weights' tiles; the result and the
    expert's part, float32."""
    size = jnp.dtype(dtype).itemsize
    return n * D * size + n * _LANE * 4 + 2 * 3 * D * _f_tile(F) * size + 2 * n * D * 4


def _kernel_fits(x_shape, w_shape, dtype) -> bool:
    """What the kernel asks of the static shapes (the platform is asked when
    the program is lowered): weights in bfloat16 or float32, D and a copy's
    columns in whole lane tiles, the call inside the VMEM budget."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    (n, D), F = x_shape, w_shape[-1]
    return (D % _LANE == 0 and _f_tile(F) % _LANE == 0
            and _kernel_vmem(n, D, F, dtype) <= _VMEM_BLOCK_BUDGET)


def _chosen_call(x, tw, gate, up, down, dtype, share, interpret=False):
    """One ``pallas_call`` named ``moe_chosen`` and no grid: the rows and
    the result whole in VMEM, the weights in HBM as ONE operand [E, 3, D,
    F] (gate, up, down transposed; a loop-invariant stack, which XLA forms
    once outside the rollout's loop). XLA's memory-space assignment
    prefetches an operand that fits VMEM whole across the token loop, pin
    or no pin to HBM, and a prefetch reads every expert: the stack of three
    does not fit. ``share``: the share of the held experts' bytes a call is
    expected to read, for the compiler's estimate. Outputs declare the
    inputs' varying mesh axes, as in ``ops/kda.py``."""
    n, D = x.shape
    E, _, F = gate.shape
    tile = _f_tile(F)
    order, count = _chosen_order(tw)
    stacked = jnp.stack([gate, up, jnp.swapaxes(down, 1, 2)], axis=1)
    vma = frozenset().union(*(jax.typeof(a).vma for a in (x, tw, stacked)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    size = jnp.dtype(dtype).itemsize
    return pl.pallas_call(
        _chosen_kernel,
        name="moe_chosen",
        in_specs=[smem, smem, whole, whole, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((n, D), F32, vma=vma),
        scratch_shapes=[
            pltpu.VMEM((2, 3, D, tile), dtype), pltpu.VMEM((n, D), F32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_kernel_vmem(n, D, F, dtype) + _VMEM_HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=int(6 * n * D * F * E * share),
            transcendentals=int(n * F * E * share),
            bytes_accessed=int(3 * D * F * E * size * share)),
        interpret=interpret,
    )(order, count.reshape(1), x.astype(dtype), tw.T[..., None].astype(F32),
      stacked)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _chosen(x, tw, gate, up, down, dtype, share, interpret=False):
    """The kernel, with the dense lines' VJP (the learner differentiates
    the fragment form; only its bootstrap token comes by here)."""
    return _chosen_call(x, tw, gate, up, down, dtype, share, interpret)


def _chosen_fwd(x, tw, gate, up, down, dtype, share, interpret=False):
    return (_chosen_call(x, tw, gate, up, down, dtype, share, interpret),
            (x, tw, gate, up, down))


def _chosen_bwd(dtype, share, interpret, residuals, cotangent):
    _, vjp = jax.vjp(lambda *a: _dense_lines(*a, dtype), *residuals)
    return vjp(cotangent)


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


def held_experts(x, ids, weights, held, num_experts, gate, up, down, dtype,
                 tile: int | None = None):
    """sum over held experts of ``w E(x)``: [N, D] float32, the number of
    tokens each held expert was sent [E_held], and whether the block was
    computed densely. ``tile``: rows of a tile of the grouped side where
    a test chooses them (``TILE`` is the chip's)."""
    n_tokens, width = x.shape
    tile = tile or TILE
    tw = held_token_weights(ids, weights, held)  # [N, E]
    load = jnp.sum(tw > 0, axis=0)
    # rows of an expert's gathered buffer: eight times its mean load, in 128s
    capacity = -(-8 * n_tokens * ids.shape[1] // (num_experts * 128)) * 128
    rows = grouped_rows(n_tokens, ids.shape[1], len(held), num_experts, tile)
    # cast once, outside the branches below
    gate, up, down = (w.astype(dtype) for w in (gate, up, down))

    # Each side under a scope of the name ``moe_sites`` counts it by, around
    # the branch function: its forward, rematerialised and backward ops keep
    # it, on whichever side of a ``lax.cond`` they are built.

    @jax.named_scope("moe_dense")
    def dense(_):
        # every held expert on every token, 2,048 tokens at a time: at a
        # fragment's size [E, N, F] is never whole
        b = math.gcd(n_tokens, 2048)
        if b == n_tokens:
            return _dense_lines(x, tw, gate, up, down, dtype)
        out = jax.lax.map(
            jax.checkpoint(lambda args: _dense_lines(*args, gate, up, down, dtype)),
            (x.reshape(-1, b, width), tw.reshape(-1, b, len(held))),
        )
        return out.reshape(n_tokens, width)

    @jax.named_scope("moe_gathered")
    def gathered(_):
        # each expert's tokens, first come first: rows past its load point
        # at row N, which reads zeros and is dropped on the way back
        order = jnp.argsort(tw.T <= 0, axis=1, stable=True)[:, :capacity]
        rows = jnp.where(
            jnp.arange(capacity)[None, :] < load[:, None], order, n_tokens
        )
        xg = _picked(x, rows)  # [E, C, D]
        wg = jnp.take_along_axis(
            jnp.pad(tw.T, ((0, 0), (0, 1))), rows, axis=1
        )
        act = _expert_act(xg, gate, up, dtype) * wg[..., None]
        y = _down(act, down, dtype)
        return jnp.zeros((n_tokens, width), F32).at[rows.reshape(-1)].add(
            y.reshape(-1, width), mode="drop"
        )

    with jax.named_scope("moe_experts"):
        if capacity < n_tokens:  # sparse routing: a buffer an expert
            x = _site_p.bind(x, path="gathered")
            fits = jnp.max(load) <= capacity
            out = jax.lax.cond(fits, gathered, dense, None)
        elif 3 * rows <= 2 * len(held) * n_tokens:
            # dense routing, a fragment's tokens: the buffer is a third
            # smaller than the dense side or more
            x = _site_p.bind(x, path="grouped")
            padded = -(-load // tile) * tile  # an expert's rows, in whole tiles
            ends = jnp.cumsum(padded)
            fits = ends[-1] <= rows

            @jax.named_scope("moe_grouped")
            def grouped(_):
                return _grouped(x, ids, weights, held, ends - padded, ends,
                                rows, tile, gate, up, down, dtype)

            out = jax.lax.cond(fits, grouped, dense, None)
        else:  # a decode step's few tokens
            fits = jnp.zeros((), bool)
            k = ids.shape[1]
            # only the held experts some token chose, where many are
            # expected idle
            if skips_idle(n_tokens, k, num_experts) and _kernel_fits(
                    x.shape, gate.shape, dtype):
                share = 1.0 - idle_share(n_tokens, k, num_experts)
                with jax.named_scope("moe_dense"):
                    out = lax.platform_dependent(
                        x, tw, gate, up, down,
                        tpu=lambda x, *a: _chosen(
                            _site_p.bind(x, path="dense_skip"), *a, dtype, share),
                        default=lambda x, *a: _dense_lines(
                            _site_p.bind(x, path="dense"), *a, dtype),
                    )
            else:
                x = _site_p.bind(x, path="dense")
                out = dense(None)
    return out, load, ~fits
