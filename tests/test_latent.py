"""The one-token latent attention (``ops/latent.py``): the kernel that reads
each env's latent rows up to ``len``, once, in the Pallas interpreter,
against the plain lines over the whole capacity; what reaches the output of
a row beyond ``len``; the VJP; the choice between the two forms and its
counter; ``models/mla.py step`` on the kernel, rotated and not. The
compile at Moonlight's widths for a described v5e is in
``tests/test_moonlight.py``, beside the scopes a profile reads."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu.models import mla, moonlight
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import latent

C = latent.CHUNK
L = 4 * C  # a capacity of several chunks
DV = 128
# a row of a latent and a rope key (Moonlight's kind), and of a latent alone
WIDTHS = {"latent_and_rope": (16, DV + 64), "latent_alone": (32, DV)}
D = 192  # the width the scores are scaled by


def operands(lengths, width, dtype, beyond=0.0, seed=0):
    """The absorbed queries and a cache whose rows beyond each env's ``len``
    hold ``beyond``."""
    H, W = WIDTHS[width]
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    length = jnp.asarray(lengths, jnp.int32)
    held = jnp.arange(L)[None, :, None] <= length[:, None, None]
    q = jax.random.normal(ks[0], (B, H, W)).astype(dtype)
    rows = jnp.where(held, jax.random.normal(ks[1], (B, L, W)), beyond).astype(dtype)
    return q, rows, length


def close(mine, ref, dtype):
    # bfloat16: the kernel rounds e^(s - m) to the products' dtype, the plain
    # lines e^(s - m) / sum: one rounding of 2^-9 each, on sums of ~1
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(mine, ref, atol=tol * float(jnp.max(jnp.abs(ref))))


EDGES = {
    "len_0": (0, 0),
    "on_a_chunks_last_row": (C - 1, 3 * C - 1),
    "on_a_chunks_first_row": (C, 2 * C),
    "capacity_less_one": (L - 1, L - 1),
    "mixed": (0, C - 2, C - 1, C, L - 1, 2 * C + 5, 1),
}


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("edge", EDGES)
def test_the_kernel_is_the_plain_lines_up_to_len(edge, dtype, width):
    q, rows, length = operands(EDGES[edge], width, dtype)
    assert latent._kernel_fits(q.shape, rows.shape, DV, dtype)
    mine = latent._kernel_step(q, rows, length, DV, D, interpret=True)
    assert mine.shape == (len(length), q.shape[1], DV) and mine.dtype == jnp.float32
    close(mine, latent._plain_latent(q, rows, length, DV, D), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("beyond", [jnp.nan, jnp.inf, 3e38])
def test_a_row_beyond_len_never_reaches_the_output(beyond, dtype):
    """Neither read into the softmax nor multiplied by zero: NaN, infinity
    or the largest finite values beyond ``len`` (in a last chunk, which is
    copied, in the chunks after it, which are not, and in the next env's
    first chunk, copied ahead) leave the result what zeros there leave it."""
    lengths = EDGES["mixed"]
    q, rows, length = operands(lengths, "latent_and_rope", dtype, beyond)
    mine = latent._kernel_step(q, rows, length, DV, D, interpret=True)
    assert bool(jnp.all(jnp.isfinite(mine)))
    _, rows0, _ = operands(lengths, "latent_and_rope", dtype, 0.0)
    clean = latent._kernel_step(q, rows0, length, DV, D, interpret=True)
    np.testing.assert_array_equal(mine, clean)
    # the plain lines multiply such a row by zero, and NaN is what they give
    if np.isnan(beyond):
        assert not bool(jnp.all(jnp.isfinite(
            latent._plain_latent(q, rows, length, DV, D))))


def test_the_kernels_vjp_is_the_plain_lines():
    """What a differentiated call on a TPU runs (the learner's bootstrap
    token): the kernel forward, here in the interpreter, the plain lines'
    backward."""
    q, rows, length = operands((0, C - 1, C, L - 1), "latent_and_rope", jnp.float32)
    mix = jax.random.normal(jax.random.PRNGKey(3), (4, q.shape[1], DV))
    scalar = lambda f: lambda q, rows: jnp.sum(f(q, rows, length, DV, D) * mix)
    with mock.patch.object(latent, "_kernel_step", functools.partial(
            latent._kernel_step, interpret=True)):
        value, mine = jax.value_and_grad(
            scalar(latent._kernel_step_vjp), argnums=(0, 1))(q, rows)
    ref_value, ref = jax.value_and_grad(
        scalar(latent._plain_latent), argnums=(0, 1))(q, rows)
    np.testing.assert_allclose(value, ref_value, rtol=1e-5)
    for a, b in zip(mine, ref):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)
    # no gradient reaches a row beyond len
    beyond = jnp.arange(L)[None, :, None] > length[:, None, None]
    assert float(jnp.max(jnp.abs(jnp.where(beyond, mine[1], 0.0)))) == 0.0
    assert float(jnp.max(jnp.abs(mine[1]))) > 0.0


def tiny_shapes(name):
    s = moonlight.SHAPES[name]
    W = s.kv_lora + s.qk_rope
    return (8, s.mla_heads, W), (8, s.max_positions, W), s.kv_lora


@pytest.mark.parametrize("q_shape, rows_shape, dv, dtype, fits", [
    ((16, 16, 576), (16, 8192, 576), 512, jnp.bfloat16, True),  # moonlight_rl's
    ((64, 32, 576), (64, 1024, 576), 512, jnp.bfloat16, True),  # kimi_linear_rl's
    ((16, 16, 576), (16, 8192, 576), 512, jnp.float32, True),
    ((2, 16, 192), (2, 4 * C, 192), 128, jnp.bfloat16, True),
    ((2, 32, 128), (2, 4 * C, 128), 128, jnp.bfloat16, True),
    (*tiny_shapes("moonlight_tiny"), jnp.float32, False),  # a latent of 24 lanes
    ((8, 2, 32), (8, 32, 32), 24, jnp.bfloat16, False),  # kimi_linear_tiny's kind
    ((2, 16, 192), (2, C + 8, 192), 128, jnp.bfloat16, False),  # not whole chunks
    ((2, 16, 192), (2, 4 * C, 192), 96, jnp.bfloat16, False),  # values not whole tiles
    ((2, 16, 192), (2, 4 * C, 192), 256, jnp.bfloat16, False),  # values wider than rows
    ((2, 16, 128), (2, 4 * C, 192), 128, jnp.bfloat16, False),  # queries not row-wide
    ((2, 16, 192), (3, 4 * C, 192), 128, jnp.bfloat16, False),
    ((2, 16, 192), (2, 4 * C, 192), 128, jnp.float16, False),
    ((0, 16, 192), (0, 4 * C, 192), 128, jnp.bfloat16, False),
    ((8192, 16, 576), (8192, 1024, 576), 512, jnp.bfloat16, False),  # over VMEM
])
def test_the_shapes_the_kernel_takes(q_shape, rows_shape, dv, dtype, fits):
    assert latent._kernel_fits(q_shape, rows_shape, dv, dtype) == fits


def mla_sites_since(before):
    now = introspect.process_record()["mla_sites"]
    return {k: now[k] - before[k] for k in now}


@pytest.mark.parametrize("capacity, differentiated", [
    (32, False), (32, True), (L, False), (L, True)])
def test_off_the_tpu_and_at_small_shapes_the_step_is_the_plain_lines(
        capacity, differentiated):
    """The other branch: by shape when the call is traced (a capacity of 32
    rows), by platform when it is lowered (whole chunks, here on a CPU),
    differentiated or not, with the plain lines' values and gradients, and
    counted as ``"step"`` once per site and program lowered."""
    H, W = WIDTHS["latent_and_rope"]
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.random.normal(ks[0], (3, H, W))
    rows = jax.random.normal(ks[1], (3, capacity, W))
    length = jnp.asarray([0, capacity - 1, capacity // 2], jnp.int32)
    scalar = lambda f: lambda q, rows: jnp.sum(f(q, rows, length, DV, D) ** 2)
    wrap = (lambda f: jax.grad(scalar(f), argnums=(0, 1))) if differentiated \
        else (lambda f: lambda q, rows: (f(q, rows, length, DV, D),))
    before = introspect.process_record()["mla_sites"]
    step = jax.jit(wrap(latent.latent_step))
    mine = step(q, rows)
    assert mla_sites_since(before) == {"step": 1, "step_kernel": 0}
    step(q, rows)  # a steady call counts nothing
    assert mla_sites_since(before) == {"step": 1, "step_kernel": 0}
    for a, b in zip(mine, jax.jit(wrap(latent._plain_latent))(q, rows)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


def test_mla_sites_count_every_site_of_a_program():
    """Two sites of one shape in one program are two (the site's lowering is
    not cached), and a second program of the same function counts again."""
    q, rows, length = operands((0, C), "latent_alone", jnp.float32)

    def two_sites(q, rows):
        once = latent.latent_step(q, rows, length, DV, D)
        return latent.latent_step(once.astype(q.dtype), rows, length, DV, D)

    before = introspect.process_record()["mla_sites"]
    jax.jit(two_sites)(q, rows)
    assert mla_sites_since(before) == {"step": 2, "step_kernel": 0}
    jax.jit(lambda *a: two_sites(*a))(q, rows)  # a second program counts again
    assert mla_sites_since(before) == {"step": 4, "step_kernel": 0}


# the latent attention's widths at a fraction of Moonlight's, rows of whole
# chunks: the kernel's rows are 128 lanes of latent and 64 of rope key
SMALL = moonlight.MoonlightShape(
    hidden=64, vocab=64, layers=("mla+dense",), mla_heads=4, qk_nope=32,
    qk_rope=64, v_head=32, kv_lora=DV, rope_theta=50000.0, dense_ffn=64,
    expert_ffn=32, shared_ffn=64, num_experts=8, held_experts=(0, 1), top_k=2,
    routed_scale=2.446, max_positions=2 * C)


@pytest.mark.parametrize("theta", [50000.0, None], ids=["rope", "nope"])
def test_the_step_on_the_kernel_is_the_step_on_the_plain_lines(theta):
    """``models/mla.py step`` as a TPU runs it (the kernel, here in the
    interpreter) against the plain lines, rotated as Moonlight's layer and
    unrotated as Kimi's, over a written row at the chunks' edges; the carry
    is the same."""
    s, dtype = SMALL, jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 8))
    p = mla.weights(lambda *d: jax.random.normal(next(keys), d) * d[-2] ** -0.5,
                    s.hidden, s)
    x = jax.random.normal(next(keys), (4, s.hidden))
    state = {"kv": jax.random.normal(
                 next(keys), (4, s.max_positions, s.kv_lora + s.qk_rope)).astype(dtype),
             "len": jnp.asarray([0, C - 1, C, 2 * C - 1], jnp.int32)}
    step = jax.jit(lambda p, x, state: mla.step(p, x, state, s, dtype, theta))
    plain_out, plain_carry = step(p, x, state)
    before = introspect.process_record()["mla_sites"]
    with mock.patch.object(jax.lax, "platform_dependent",
                           lambda *a, tpu, default: tpu(*a)), \
            mock.patch.object(latent, "_kernel_step", functools.partial(
                latent._kernel_step, interpret=True)):
        out, carry = jax.jit(
            lambda p, x, state: mla.step(p, x, state, s, dtype, theta))(p, x, state)
    assert mla_sites_since(before) == {"step": 0, "step_kernel": 1}
    close(out, plain_out, dtype)
    assert jax.tree.all(jax.tree.map(jnp.array_equal, carry, plain_carry))
