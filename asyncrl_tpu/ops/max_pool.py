"""3x3 / stride 2 / SAME max-pool whose backward does not need its input.

``nn.max_pool`` differentiates to XLA's ``select_and_scatter``, which reads
the pre-pool activation a second time only to find again which of nine
elements was the maximum, so that tensor (the largest activation of an
IMPALA-CNN section) is kept alive from forward to backward for the pool
alone. :func:`max_pool_3x3_s2` is the same function with a ``custom_vjp``
that, where it can, runs a pair of Pallas kernels instead: the forward
writes the pooled value and, one byte per *output* element, the window
position of the maximum (0-8 row-major, the first maximum on ties, a
padded tap never: what ``select_and_scatter`` with ``ge`` selects); the
backward reads that byte and the cotangent and writes every input element
once, as the float32 sum of the at most four windows that cover it.

Layout: the kernels work on the ``[H, W, C, N]`` view (N = the flattened
leading dims). XLA:TPU already keeps these activations with the batch in
the lanes and the channels in the sublanes (``{0,3,2,1:T(8,128)(2,1)}``),
so the transposes around the calls are bitcasts, not copies, and a stride-2
window over H and W is address arithmetic over whole (C, 128) tiles
(pinned by tests/test_max_pool.py against a described v5e).

Which path a differentiated call takes is read from what can be observed:

- the static shape, when the call is traced: the kernels need the lanes
  full (N % 128 == 0; ``grad_accum`` chunks and PPO minibatches usually are
  not) and one whole image per 128 lanes, double-buffered, inside VMEM;
- the platform, when the program is lowered (``lax.platform_dependent``):
  anything but a TPU keeps ``nn.max_pool``'s own VJP.

A call that is not differentiated is exactly ``nn.max_pool``. The kernels'
comparisons are ordered, so a NaN is kept only where it is a window's first
tap (XLA's ``max`` propagates every one); finite inputs pool bit-equal.
``obs.introspect.process_record()["pool_sites"]`` counts, per program
lowered, the differentiated sites that took the kernels and those that
fell back.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops.site import site_primitive

_LANE = 128
# Sublane rows of one (rows, 128) tile, by itemsize.
_TILE_ROWS = {4: 8, 2: 16, 1: 32}
# Double-buffered blocks of one grid step must fit this; the v5e's VMEM is
# 128 MiB, and the rest is the compiler's (spills, semaphores).
_VMEM_BLOCK_BUDGET = 96 * 1024 * 1024
_VMEM_HEADROOM = 16 * 1024 * 1024


def _reference(x: jax.Array) -> jax.Array:
    return nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")


def _same_geometry(size: int) -> tuple[int, int]:
    """(output size, padding before) of a 3-wide stride-2 SAME window: even
    sizes pad one after, odd sizes one on each side."""
    return -(-size // 2), size % 2


def _taps(i: int, size: int) -> tuple[int, ...]:
    """The taps of window ``i`` along one axis that lie on the image."""
    pad = _same_geometry(size)[1]
    return tuple(d for d in range(3) if 0 <= 2 * i - pad + d < size)


# ----------------------------------------------------------------- kernels


def _fwd_kernel(x_ref, y_ref, i_ref):
    """One block of 128 lanes: x_ref (H, W, C, L) -> y_ref, i_ref
    (OH, OW, C, L). Separable: the 3-tap maximum along W of each input row
    (value and tap), then along H; a row's result is carried down as the
    next window's top row, so each input tile is loaded 1.5 times and
    compared once. Strict ``>`` in row-major order keeps the first maximum;
    a tap the SAME padding adds is left out, not compared. Only the first
    and last window of an axis can have one, so those are peeled and the
    loops between them run over whole windows."""
    H, W = x_ref.shape[:2]
    (OH, top), (OW, left) = _same_geometry(H), _same_geometry(W)

    def row_max(r, j, taps):
        best = tap = None
        for dj in taps:
            t = x_ref[r, 2 * j - left + dj].astype(jnp.float32)
            if best is None:
                best, tap = t, jnp.full(t.shape, dj, jnp.int32)
            else:
                take = t > best
                best = jnp.where(take, t, best)
                tap = jnp.where(take, dj, tap)
        return best, tap

    def window(i, j, carried, rows, taps):
        best = pos = below = None
        for di in rows:
            if di == 0:
                m, tap = carried
            else:
                m, tap = below = row_max(2 * i - top + di, j, taps)
                tap = tap + 3 * di
            if best is None:
                best, pos = m, tap
            else:
                take = m > best
                best = jnp.where(take, m, best)
                pos = jnp.where(take, tap, pos)
        y_ref[i, j] = best.astype(y_ref.dtype)
        i_ref[i, j] = pos.astype(i_ref.dtype)
        return below  # where 2 is in rows: the next window's top row

    def column(j, taps):
        first = _taps(0, H)
        carried = row_max(0, j, taps) if 0 in first else None
        carried = window(0, j, carried, first, taps)
        if OH == 1:
            return
        carried = lax.fori_loop(
            1, OH - 1,
            lambda i, c: window(i, j, c, (0, 1, 2), taps), carried)
        window(OH - 1, j, carried, _taps(OH - 1, H), taps)

    def whole_column(j, _):
        column(j, (0, 1, 2))

    column(0, _taps(0, W))
    if OW > 1:
        lax.fori_loop(1, OW - 1, whole_column, None)
        column(OW - 1, _taps(OW - 1, W))


def _bwd_kernel(g_ref, i_ref, dx_ref):
    """Gather form: g_ref, i_ref (OH, OW, C, L) -> dx_ref (H, W, C, L), every
    input tile written once. In padded coordinates (row + top, column +
    left) input (2i + a, 2j + b) is tap 3a + b of window (i, j), tap
    3a + b + 2 of (i, j-1) when b == 0, tap 3a + b + 6 of (i-1, j) when
    a == 0: up to four windows for the even-even element, one for the
    odd-odd. Summed in float32, rounded once."""
    H, W = dx_ref.shape[:2]
    (OH, top), (OW, left) = _same_geometry(H), _same_geometry(W)

    def load(i, j):
        return (g_ref[i, j].astype(jnp.float32),
                i_ref[i, j].astype(jnp.int32))

    def total(*terms):
        acc = None
        for neighbour, tap in terms:
            if neighbour is not None:  # a window beyond the first row/column
                g, pos = neighbour
                t = jnp.where(pos == tap, g, 0.0)
                acc = t if acc is None else acc + t
        return acc

    def quad(i, j, here, west, north, northwest, no_row0, no_col0):
        """Padded rows 2i, 2i+1 x columns 2j, 2j+1; ``no_row0`` / ``no_col0``
        say that the first of them is padding (first quad, odd sizes)."""
        sums = {
            (0, 0): ((here, 0), (west, 2), (north, 6), (northwest, 8)),
            (0, 1): ((here, 1), (north, 7)),
            (1, 0): ((here, 3), (west, 5)),
            (1, 1): ((here, 4),),
        }
        for (a, b), terms in sums.items():
            if (no_row0 and a == 0) or (no_col0 and b == 0):
                continue
            dx_ref[2 * i + a - top, 2 * j + b - left] = total(*terms).astype(
                dx_ref.dtype)

    def column(j, first_column):
        def step(i, norths, first_row=False):
            here = load(i, j)
            west = None if first_column else load(i, j - 1)
            quad(i, j, here, west, *norths,
                 first_row and top == 1, first_column and left == 1)
            return here, west

        carried = step(0, (None, None), first_row=True)
        if OH > 1:
            lax.fori_loop(1, OH, step, carried)

    def later_column(j, _):
        column(j, False)

    column(0, True)
    if OW > 1:
        lax.fori_loop(1, OW, later_column, None)


# ------------------------------------------------------------ kernel calls


def _vmem_bytes(operands) -> int:
    """Double-buffered VMEM of one grid step: ``operands`` are the ``(H, W,
    C, dtype)`` of a call's arrays, each blocked as (H, W, C, 128) with C
    rounded up to whole (rows, 128) tiles."""
    total = 0
    for h, w, c, dtype in operands:
        itemsize = jnp.dtype(dtype).itemsize
        rows = _TILE_ROWS[itemsize]
        total += h * w * -(-c // rows) * rows * _LANE * itemsize
    return 2 * total


def _kernel_fits(shape: tuple[int, ...], dtype) -> bool:
    """What the kernels ask of a static shape (the platform is asked when
    the program is lowered): full lanes, a tiled float dtype, one image per
    128 lanes inside the VMEM budget."""
    if len(shape) < 4 or jnp.dtype(dtype) not in (jnp.float32, jnp.bfloat16):
        return False
    n, (H, W, C) = math.prod(shape[:-3]), shape[-3:]
    pooled = (_same_geometry(H)[0], _same_geometry(W)[0], C)
    return (n > 0 and n % _LANE == 0 and min(H, W, C) > 0
            and _vmem_bytes([(H, W, C, dtype), (*pooled, dtype),
                             (*pooled, jnp.int8)]) <= _VMEM_BLOCK_BUDGET)


def _call(kernel, name, inputs, out_hw, out_dtypes, interpret):
    """``pallas_call`` over blocks of one whole image x 128 lanes of the
    ``[H, W, C, N]`` view; outputs declare the inputs' varying mesh axes
    (``ops/pallas_scan.py _out_struct``: the checked shard_map wants it)."""
    C, N = inputs[0].shape[2:]
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    operands = [(*x.shape[:3], x.dtype) for x in inputs] + [
        (*out_hw, C, dtype) for dtype in out_dtypes]

    def spec(h, w, c, _):
        return pl.BlockSpec((h, w, c, _LANE), lambda n: (0, 0, 0, n),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        name=name,
        grid=(N // _LANE,),
        in_specs=[spec(*o) for o in operands[:len(inputs)]],
        out_specs=[spec(*o) for o in operands[len(inputs):]],
        out_shape=[jax.ShapeDtypeStruct((*out_hw, C, N), dtype, vma=vma)
                   for dtype in out_dtypes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_bytes(operands) + _VMEM_HEADROOM,
        ),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=sum(h * w * c * N * jnp.dtype(dtype).itemsize
                               for h, w, c, dtype in operands)),
        interpret=interpret,
    )(*inputs)


def _kernel_fwd(x: jax.Array, interpret: bool = False):
    """``x [..., H, W, C]`` -> (pooled ``[..., OH, OW, C]``, positions int8
    ``[OH, OW, C, N]``, kept in the kernels' own view)."""
    lead, (H, W, C) = x.shape[:-3], x.shape[-3:]
    out_hw = (_same_geometry(H)[0], _same_geometry(W)[0])
    view = jnp.transpose(x.reshape(-1, H, W, C), (1, 2, 3, 0))
    y, pos = _call(_fwd_kernel, "max_pool_fwd", [view], out_hw,
                   [x.dtype, jnp.int8], interpret)
    # The barrier keeps XLA's simplifier from cancelling this transpose
    # against the one a later VJP takes of anything computed from y: the
    # residual blocks' x + f(x) would otherwise be computed twice, once in
    # each logical shape, and the backward fusions read five activations
    # where three do (1.9 GB more an update in section 0 alone).
    y = lax.optimization_barrier(jnp.transpose(y, (3, 0, 1, 2)))
    return y.reshape(*lead, *out_hw, C), pos


def _kernel_bwd(pos: jax.Array, g: jax.Array, in_shape: tuple[int, ...],
                interpret: bool = False) -> jax.Array:
    """The cotangent of ``x`` (shape ``in_shape``) from the positions
    :func:`_kernel_fwd` saved and the cotangent ``g`` of its pooled output."""
    view = jnp.transpose(g.reshape(-1, *g.shape[-3:]), (1, 2, 3, 0))
    (dx,) = _call(_bwd_kernel, "max_pool_bwd", [view, pos], in_shape[-3:-1],
                  [g.dtype], interpret)
    return jnp.transpose(dx, (3, 0, 1, 2)).reshape(in_shape)


# -------------------------------------------------------------- the counter

# Which path a differentiated site ended on is known where it is lowered.
_site_p = site_primitive("max_pool_site", introspect.count_pool_site)


# ------------------------------------------------------------ the function


@jax.custom_vjp
def max_pool_3x3_s2(x: jax.Array) -> jax.Array:
    """``nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")`` over
    ``x [..., H, W, C]``; see the module docstring for its VJP."""
    return _reference(x)


def _reference_fwd(x):
    return _reference(_site_p.bind(x, path="fallback"))


def _fwd(x):
    if not _kernel_fits(x.shape, x.dtype):
        return _reference_fwd(x), (x, None)

    def no_positions(x):
        # Branches agree in type; nothing reads these (dead code off a TPU).
        y = _reference_fwd(x)
        view = jnp.transpose(y.reshape(-1, *y.shape[-3:]), (1, 2, 3, 0))
        return y, jnp.zeros_like(view, jnp.int8)

    y, pos = lax.platform_dependent(
        x, tpu=lambda x: _kernel_fwd(_site_p.bind(x, path="kernel")),
        default=no_positions)
    return y, (x, pos)


def _reference_bwd(x, g):
    return jax.vjp(_reference, x)[1](g)[0]


def _bwd(residuals, g):
    x, pos = residuals
    if pos is None:
        return (_reference_bwd(x, g),)
    # x is a residual only for the branch a TPU program drops.
    return (lax.platform_dependent(
        x, pos, g,
        tpu=lambda x, pos, g: _kernel_bwd(pos, g, x.shape),
        default=lambda x, pos, g: _reference_bwd(x, g)),)


max_pool_3x3_s2.defvjp(_fwd, _bwd)
