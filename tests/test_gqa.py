"""The one-token grouped-query attention (``ops/gqa.py``): the kernel that
reads the cache up to ``len``, in the Pallas interpreter, against the plain
lines over the whole capacity, and under a mask of chosen rows against
``ops/dsa.py``'s masked products; what reaches the output of a row beyond
``len``; the VJP; the choice between the two forms and its counter."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import dsa, gqa

C = gqa.CHUNK
L = 4 * C  # a capacity of several chunks
H, DH = 8, 64


def operands(lengths, groups, dtype, beyond=0.0, seed=0):
    """``q`` and a cache of ``groups`` key-value heads a row whose rows
    beyond each env's ``len`` hold ``beyond``."""
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    length = jnp.asarray(lengths, jnp.int32)
    held = jnp.arange(L)[None, :, None] <= length[:, None, None]
    q = jax.random.normal(ks[0], (B, H, DH))
    keys, values = (
        jnp.where(held, jax.random.normal(k, (B, L, groups * DH)), beyond).astype(dtype)
        for k in ks[1:])
    return q, keys, values, length


def close(mine, ref, dtype):
    # bfloat16: the kernel rounds e^(s - m) to the products' dtype, the plain
    # lines e^(s - m) / sum: one rounding of 2^-9 each, on sums of ~1
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(mine, ref, atol=tol * float(jnp.max(jnp.abs(ref))))


EDGES = {
    "len_0": (0, 0),
    "one_short_of_a_chunks_edge": (C - 2, 2 * C - 2),
    "on_the_edge": (C - 1, 3 * C - 1),
    "one_past_it": (C, 2 * C),
    "capacity_less_one": (L - 1, L - 1),
    "mixed": (0, C - 2, C - 1, C, L - 1, 2 * C + 5, 1),
}


@pytest.mark.parametrize("groups", [2, 8])  # rows of 128 and of 512 lanes
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("edge", EDGES)
def test_the_kernel_is_the_plain_lines_up_to_len(edge, dtype, groups):
    assert gqa._kernel_fits((len(EDGES[edge]), H, DH), (len(EDGES[edge]), L, groups * DH), dtype)
    q, keys, values, length = operands(EDGES[edge], groups, dtype)
    mine = gqa._kernel_step(q, keys, values, length, interpret=True)
    assert mine.shape == (len(length), H, DH) and mine.dtype == jnp.float32
    close(mine, gqa._plain_step(q, keys, values, length), dtype)


def rows_up_to(length):
    return jnp.arange(L)[None, :] <= jnp.asarray(length)[:, None]


def some_rows(length, seed=7):
    """A third of each env's rows up to ``len``, the current row among them."""
    length = jnp.asarray(length)
    drawn = jax.random.uniform(jax.random.PRNGKey(seed), (len(length), L)) < 1 / 3
    return (drawn & rows_up_to(length)).at[jnp.arange(len(length)), length].set(True)


def without(chosen, env, rows):
    return chosen.at[env, rows].set(False)


# name -> (lengths, the mask of chosen rows from them)
CHOSEN = {
    "a_first_chunk_with_no_chosen_row": (
        (C, 2 * C + 5, L - 1),
        lambda n: without(some_rows(n), slice(None), slice(0, C))),
    "a_middle_chunk_with_none": (
        (2 * C, 3 * C + 9, L - 1),
        lambda n: without(some_rows(n), slice(None), slice(C, 2 * C))),
    "a_last_chunk_whose_only_chosen_row_is_the_current_one": (
        (C, 2 * C + 5, L - 1),
        lambda n: (some_rows(n) & (jnp.arange(L)[None] < (jnp.asarray(n)[:, None] // C) * C)
                   ).at[jnp.arange(len(n)), jnp.asarray(n)].set(True)),
    "the_current_row_not_chosen": (
        (1, C, 2 * C + 5, L - 1),
        lambda n: some_rows(n).at[jnp.arange(len(n)), jnp.asarray(n)].set(False)
        .at[:, 0].set(True)),
    "every_row_chosen": (EDGES["mixed"], rows_up_to),
    "len_on_a_chunks_last_row": ((C - 1, 3 * C - 1, L - 1), some_rows),
    "len_on_a_chunks_first_row": ((C, 2 * C, 3 * C), some_rows),
    "len_0": ((0, 0), some_rows),
    "mixed": (EDGES["mixed"], some_rows),
}


@pytest.mark.parametrize("groups", [2, 8])  # rows of 128 and of 512 lanes
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", CHOSEN)
def test_under_a_mask_the_kernel_is_the_masked_products_over_the_capacity(
        case, dtype, groups):
    """A row counts if it is at or before ``len`` and chosen. A chunk with no
    chosen row (the first of a long episode can be one, while the running
    maximum is still -inf) leaves the running sums as they were."""
    lengths, choose = CHOSEN[case]
    assert gqa._kernel_fits(
        (len(lengths), H, DH), (len(lengths), L, groups * DH), dtype, masked=True)
    q, keys, values, length = operands(lengths, groups, dtype)
    chosen = choose(lengths)
    assert bool(jnp.all(jnp.any(chosen, axis=-1)))
    mine = gqa._kernel_step(q, keys, values, length, chosen, interpret=True)
    assert mine.shape == (len(length), H, DH) and mine.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(mine)))
    close(mine, dsa._attend_rows(q, keys, values, chosen), dtype)
    # rows chosen beyond len do not count: the mask is taken up to len
    np.testing.assert_array_equal(mine, gqa._kernel_step(
        q, keys, values, length, chosen | ~rows_up_to(length), interpret=True))
    if case == "every_row_chosen":  # the kernel without a mask, to the bit
        np.testing.assert_array_equal(
            mine, gqa._kernel_step(q, keys, values, length, interpret=True))


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("beyond", [jnp.nan, jnp.inf, 3e38])
def test_a_row_beyond_len_never_reaches_the_output(beyond, dtype, masked):
    """Neither read into the softmax nor multiplied by zero: NaN, infinity
    or the largest finite values beyond ``len`` (in a last chunk, which is
    copied, and in the chunks after it, which are not) leave the result
    what zeros there leave it, with a mask of chosen rows (which chooses
    rows beyond ``len`` too) as without."""
    lengths = EDGES["mixed"]
    q, keys, values, length = operands(lengths, 2, dtype, beyond)
    chosen = (some_rows(lengths) | ~rows_up_to(lengths),) if masked else ()
    mine = gqa._kernel_step(q, keys, values, length, *chosen, interpret=True)
    assert bool(jnp.all(jnp.isfinite(mine)))
    _, keys0, values0, _ = operands(lengths, 2, dtype, 0.0)
    clean = gqa._kernel_step(q, keys0, values0, length, *chosen, interpret=True)
    np.testing.assert_array_equal(mine, clean)
    # the plain lines multiply such a row by zero, and NaN is what they give
    if np.isnan(beyond):
        assert not bool(jnp.all(jnp.isfinite(gqa._plain_step(q, keys, values, length))))


def test_the_kernels_vjp_is_the_plain_lines():
    """What a differentiated call on a TPU runs (the learner's bootstrap
    token): the kernel forward, here in the interpreter, the plain lines'
    backward."""
    q, keys, values, length = operands((0, C - 1, C, L - 1), 2, jnp.float32)
    mix = jax.random.normal(jax.random.PRNGKey(3), q.shape)
    scalar = lambda f: lambda *a: jnp.sum(f(*a, length) * mix)
    with mock.patch.object(
            gqa, "_kernel_step", functools.partial(gqa._kernel_step, interpret=True)):
        value, mine = jax.value_and_grad(
            scalar(gqa._kernel_step_vjp), argnums=range(3))(q, keys, values)
    ref_value, ref = jax.value_and_grad(
        scalar(gqa._plain_step), argnums=range(3))(q, keys, values)
    np.testing.assert_allclose(value, ref_value, rtol=1e-5)
    for a, b in zip(mine, ref):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)
    # no gradient reaches a row beyond len
    beyond = jnp.arange(L)[None, :, None] > length[:, None, None]
    assert float(jnp.max(jnp.abs(jnp.where(beyond, mine[1], 0.0)))) == 0.0
    assert float(jnp.max(jnp.abs(mine[1]))) > 0.0


@pytest.mark.parametrize("q_shape, rows_shape, dtype, fits", [
    ((128, 32, 64), (128, 2048, 512), jnp.bfloat16, True),  # lfm2_moe_rl's
    ((128, 32, 64), (128, 2048, 512), jnp.float32, True),
    ((2, 8, 64), (2, 4 * C, 128), jnp.bfloat16, True),
    ((8, 4, 16), (8, 32, 32), jnp.float32, False),  # lfm2_moe_tiny's: rows of 32 lanes
    ((8, 4, 16), (8, 32, 32), jnp.bfloat16, False),
    ((2, 8, 64), (2, C + 8, 128), jnp.bfloat16, False),  # not whole chunks
    ((2, 8, 64), (2, 4 * C, 192), jnp.bfloat16, False),  # half a lane tile
    ((2, 6, 64), (2, 4 * C, 256), jnp.bfloat16, False),  # heads not in whole groups
    ((2, 8, 64), (2, 4 * C, 128), jnp.float16, False),
    ((2, 8, 64), (3, 4 * C, 128), jnp.bfloat16, False),
    ((0, 8, 64), (0, 4 * C, 128), jnp.bfloat16, False),
    ((4096, 32, 64), (4096, 2048, 512), jnp.bfloat16, False),  # the laid queries over VMEM
])
def test_the_shapes_the_kernel_takes(q_shape, rows_shape, dtype, fits):
    assert gqa._kernel_fits(q_shape, rows_shape, dtype) == fits


@pytest.mark.parametrize("q_shape, rows_shape, fits, masked_fits", [
    ((16, 32, 128), (16, 8192, 512), True, True),  # keye_moe_rl's: a mask of 512 KB
    ((8, 4, 16), (8, 32, 32), False, False),  # keye_moe_tiny's
    ((1024, 32, 64), (1024, 8192, 512), True, False),  # the mask takes it over VMEM
])
def test_the_mask_of_chosen_rows_counts_in_the_kernels_vmem(
        q_shape, rows_shape, fits, masked_fits):
    assert gqa._kernel_fits(q_shape, rows_shape, jnp.bfloat16) == fits
    assert gqa._kernel_fits(q_shape, rows_shape, jnp.bfloat16, masked=True) == masked_fits
    B, L_ = rows_shape[:2]
    assert (gqa._vmem(q_shape, rows_shape, jnp.bfloat16, masked=True)
            - gqa._vmem(q_shape, rows_shape, jnp.bfloat16)) >= B * L_ * 4


def gqa_sites_since(before):
    now = introspect.process_record()["gqa_sites"]
    return {k: now[k] - before[k] for k in now}


@pytest.mark.parametrize("groups, capacity, differentiated", [
    (2, 32, False), (2, 32, True), (2, L, False), (2, L, True)])
def test_off_the_tpu_and_at_small_shapes_the_step_is_the_plain_lines(
        groups, capacity, differentiated):
    """The other branch: by shape when the call is traced (a capacity of 32
    rows), by platform when it is lowered (whole chunks, here on a CPU),
    differentiated or not, with the plain lines' values and gradients, and
    counted as ``"step"`` once per site and program lowered."""
    B = 3
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, DH))
    keys, values = (jax.random.normal(k, (B, capacity, groups * DH)) for k in ks[1:])
    length = jnp.asarray([0, capacity - 1, capacity // 2], jnp.int32)
    scalar = lambda f: lambda *a: jnp.sum(f(*a, length) ** 2)
    wrap = (lambda f: jax.grad(scalar(f), argnums=range(3))) if differentiated \
        else (lambda f: lambda *a: (f(*a, length),))
    before = introspect.process_record()["gqa_sites"]
    step = jax.jit(wrap(gqa.gqa_step))
    mine = step(q, keys, values)
    assert gqa_sites_since(before) == {"step": 1, "step_kernel": 0}
    step(q, keys, values)  # a steady call counts nothing
    assert gqa_sites_since(before) == {"step": 1, "step_kernel": 0}
    for a, b in zip(mine, jax.jit(wrap(gqa._plain_step))(q, keys, values)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


def test_gqa_sites_count_every_site_of_a_program():
    """Two sites of one shape in one program are two (the site's lowering is
    not cached), and a second program of the same function counts again."""
    q, keys, values, length = operands((0, C), 2, jnp.float32)

    def two_sites(q, keys, values):
        return gqa.gqa_step(gqa.gqa_step(q, keys, values, length), keys, values, length)

    before = introspect.process_record()["gqa_sites"]
    jax.jit(two_sites)(q, keys, values)
    assert gqa_sites_since(before) == {"step": 2, "step_kernel": 0}
    jax.jit(lambda *a: two_sites(*a))(q, keys, values)  # a second program counts again
    assert gqa_sites_since(before) == {"step": 4, "step_kernel": 0}
