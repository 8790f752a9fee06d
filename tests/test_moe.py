"""The expert layer's decode side (``ops/moe.py held_experts``, its few
tokens): the held experts that some token chose, and no other, by the
kernel ``moe_chosen`` (here under the Pallas interpreter), against the dense
lines it replaces on a TPU; the rule that decides where it does; and the
learner's count of what a decode step skips, ``moe_step_idle_share``."""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from asyncrl_tpu.configs import presets
from asyncrl_tpu.envs import registry
from asyncrl_tpu.models import (
    keye_moe, kimi_linear, lfm2_moe, moonlight, networks, seq_common)
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import moe

BF16 = jnp.bfloat16

# decode shapes scaled from the cells': (tokens, D, F, held experts). Keye's
# F of 384 takes three copies of 128 columns an expert, Moonlight's 640 five
SHAPES = {"keye": (16, 256, 384, 16), "moonlight": (16, 256, 640, 8)}
# held experts some token chose: none, some, every one
ROUTINGS = {"none": lambda E: [], "some": lambda E: list(range(1, E, 3)),
            "all": lambda E: list(range(E))}


def decode_case(shape, chosen, seed=0):
    n, D, F, E = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (n, D))
    gate, up = ((jax.random.normal(k, (E, D, F)) * D ** -0.5).astype(BF16)
                for k in keys[1:3])
    down = (jax.random.normal(keys[3], (E, F, D)) * F ** -0.5).astype(BF16)
    # each token chooses one or two of the chosen experts
    pick = jax.random.uniform(keys[4], (n, E)) < 0.3
    pick = pick | (jnp.arange(E)[None, :] == jnp.asarray(
        [chosen[i % len(chosen)] if chosen else -1 for i in range(n)])[:, None])
    hit = pick & jnp.isin(jnp.arange(E), jnp.asarray(chosen, jnp.int32))[None, :]
    tw = jnp.where(hit, jax.random.uniform(keys[5], (n, E), minval=0.1), 0.0)
    return x, tw, gate, up, down


@pytest.mark.parametrize("routing", list(ROUTINGS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_chosen_experts_give_the_dense_lines_result(shape, routing):
    """The dense lines add an exact zero for an expert no token chose, so
    they ARE the sum of the chosen experts' parts alone, bit for bit; the
    kernel computes those parts (an F tile at a time) and adds them in held
    order: the same within the rounding of the down product's bfloat16
    operand (its float32 gate and up products are summed in another order,
    which can round an element of ``silu(gate x) * up x`` to the next
    bfloat16), and exactly zero where no held expert was chosen."""
    E = SHAPES[shape][-1]
    chosen = ROUTINGS[routing](E)
    x, tw, gate, up, down = decode_case(shape, chosen)
    dense = moe._dense_lines(x, tw, gate, up, down, BF16)
    parts = [moe._dense_lines(x, tw[:, e:e + 1], gate[e:e + 1], up[e:e + 1],
                              down[e:e + 1], BF16) for e in chosen]
    np.testing.assert_array_equal(
        dense, functools.reduce(jnp.add, parts, jnp.zeros_like(dense)))
    out = moe._chosen_call(x, tw, gate, up, down, BF16, 0.5, interpret=True)
    if not chosen:
        np.testing.assert_array_equal(out, jnp.zeros_like(dense))
    act = moe._expert_act(x, gate, up, BF16) * tw.T[..., None]
    rounding = 2.0 ** -8 * jnp.sum(jnp.einsum(
        "enf,efd->end", jnp.abs(act), jnp.abs(down.astype(jnp.float32))), axis=0)
    assert bool(jnp.all(jnp.abs(out - dense) <= rounding + 1e-6))


def test_the_chosen_order_puts_the_chosen_first_in_held_order():
    tw = jnp.zeros((3, 6)).at[0, 4].set(0.5).at[1, 1].set(0.2).at[2, 4].set(0.1)
    ids, count = moe._chosen_order(tw)
    assert int(count) == 2
    assert [int(i) for i in ids] == [1, 4, 0, 2, 3, 5]


def test_the_kernels_vjp_is_the_dense_lines():
    """The learner differentiates the fragment form; its bootstrap token
    comes by the decode side under ``value_and_grad``: every gradient of
    the kernel's site is the dense lines' own."""
    x, tw, gate, up, down = decode_case("keye", ROUTINGS["some"](16))
    mix = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def grads(form):
        def loss(*a):
            return jnp.sum(form(*a) * mix)
        return jax.jit(jax.value_and_grad(loss, argnums=range(5)))(
            x, tw, gate, up, down)

    value, mine = grads(lambda *a: moe._chosen(*a, BF16, 0.5, True))
    ref_value, ref = grads(lambda *a: moe._dense_lines(*a, BF16))
    assert float(value) == pytest.approx(float(ref_value), rel=1e-5)
    for name, a, b in zip(("x", "tw", "gate", "up", "down"), mine, ref):
        np.testing.assert_array_equal(a, b, err_msg=name)


# where the rule engages: each MoE preset's cell, its decode step's tokens
CELLS = [("keye_moe_rl", keye_moe.SHAPES, True),
         ("moonlight_rl", moonlight.SHAPES, True),
         ("kimi_linear_rl", kimi_linear.SHAPES, False),
         ("lfm2_moe_rl", lfm2_moe.SHAPES, False)]


@pytest.mark.parametrize("preset, shapes, engages", CELLS)
def test_the_rule_at_each_cells_decode_step(preset, shapes, engages):
    cfg = presets.get(preset)
    s = shapes[cfg.seq_model]
    n = cfg.num_envs
    assert moe.skips_idle(n, s.top_k, s.num_experts) == engages
    # the learner's 2,048-token blocks never do
    assert not moe.skips_idle(2048, s.top_k, s.num_experts)
    if engages:  # and the kernel takes the cell's widths
        E, D, F = len(s.held_experts), s.hidden, s.expert_ffn
        assert moe._kernel_fits((n, D), (E, D, F), BF16)


def test_the_expected_idle_shares():
    assert moe.idle_share(16, 8, 128) == pytest.approx(0.356, abs=1e-3)
    assert moe.idle_share(16, 6, 64) == pytest.approx(0.207, abs=1e-3)
    assert moe.idle_share(64, 8, 256) == pytest.approx(0.131, abs=1e-3)
    assert moe.idle_share(128, 4, 32) < 1e-7


def test_the_decode_side_on_the_cpu_is_the_dense_lines_and_counts_dense():
    """Off a TPU the engaged site keeps the dense lines, bit for bit, and
    ``moe_sites`` counts ``"dense"`` (a described TPU counts
    ``"dense_skip"``: ``tests/test_max_pool.py``)."""
    n, D, F, E = SHAPES["keye"]
    x, tw, gate, up, down = decode_case("keye", ROUTINGS["some"](E))
    ids = jnp.argsort(-tw, axis=1)[:, :2].astype(jnp.int32)
    weights = jnp.take_along_axis(tw, ids, axis=1)
    assert moe.skips_idle(n, 2, 128)
    before = introspect.process_record()["moe_sites"]
    out, load, dense = jax.jit(
        lambda *a: moe.held_experts(*a[:3], tuple(range(E)), 128, *a[3:], BF16)
    )(x, ids, weights, gate, up, down)
    after = introspect.process_record()["moe_sites"]
    assert {k: after[k] - before[k] for k in after} == {
        "grouped": 0, "gathered": 0, "dense": 1, "dense_skip": 0}
    tw_ids = moe.held_token_weights(ids, weights, tuple(range(E)))
    np.testing.assert_array_equal(
        out, jax.jit(lambda *a: moe._dense_lines(*a, BF16))(
            x, tw_ids, gate, up, down))
    np.testing.assert_array_equal(load, jnp.sum(tw_ids > 0, axis=0))
    assert bool(dense)  # the decode side is every held expert, as before


# ------------------------------------------- moe_step_idle_share by hand


@dataclasses.dataclass(frozen=True)
class RoutedShape(seq_common.TrunkScales):
    """Two expert layers and no mixer: each token's hidden state is its
    one-hot embedding, so the router's row for a token decides its experts
    in every layer."""

    layers: tuple = ("none+moe", "none+moe")
    hidden: int = 8
    eps: float = 1e-6
    block_tokens: int = 3  # a fragment of 3 steps: one env a block
    num_experts: int = 8
    held_experts: tuple = (0, 1, 2, 3)
    top_k: int = 2
    routed_scale: float = 1.0


class RoutedPolicy(seq_common.SeqPolicyBase):
    def _mixer(self, p, mixer, x, state, done):
        return jnp.zeros_like(x), state, {}


# token -> the two experts its router row chooses (held: 0-3)
ROUTES = {0: (0, 1), 1: (2, 5), 2: (4, 5), 3: (3, 6)}


def routed_params(shape):
    D, E = shape.hidden, shape.num_experts
    router = jnp.full((D, E), -10.0)
    for token, experts in ROUTES.items():
        router = router.at[token, jnp.asarray(experts)].set(10.0)
    held = len(shape.held_experts)
    layer = {"norm_mixer": jnp.ones(D), "norm_ffn": jnp.ones(D), "none": {},
             "ffn": {"router": router, "experts": {
                 # zero experts: the residual stream stays the embedding
                 "gate": jnp.zeros((held, D, 4)), "up": jnp.zeros((held, D, 4)),
                 "down": jnp.zeros((held, 4, D))}}}
    return {"embed": jnp.eye(D), "final_norm": jnp.ones(D),
            "value": {"kernel": jnp.zeros((D, 1)), "bias": jnp.zeros(1)},
            **{f"layer_{i}": layer for i in range(len(shape.layers))}}


def test_step_idle_share_counted_by_hand():
    """Three steps of two envs, one env a block, two expert layers routed
    alike. Step 0: tokens 0 and 2 choose held experts 0 and 1 (and 4, 5,
    not held): 2 and 3 idle. Step 1: tokens 2 and 2: all four idle. Step 2:
    tokens 1 and 3 choose 2 and 3: 0 and 1 idle. 8 of 12 (step, held
    expert) pairs a layer, in both layers: 16 of 24."""
    shape = RoutedShape()
    policy = RoutedPolicy(shape)
    params = routed_params(shape)
    tokens = jnp.asarray([[0, 2], [2, 2], [1, 3]], jnp.int32)
    done = jnp.zeros(tokens.shape, bool)
    core = seq_common.SeqCore(({}, {}))
    assert moe.skips_idle(2, shape.top_k, shape.num_experts)
    *_, aux = policy.fragment(params, tokens, done, core)
    assert aux["moe_step_idle_share"] == np.float32(16 / 24)
    assert float(aux["moe_local_assignments"]) == 2 * 4  # 2 + 0 + 2 a layer
    # ten envs a step: (6/8) ** 10 < 0.1, the rule does not engage and the
    # update has no such metric
    tokens = jnp.tile(tokens, (1, 5))
    assert not moe.skips_idle(10, shape.top_k, shape.num_experts)
    *_, aux = policy.fragment(params, tokens, jnp.zeros(tokens.shape, bool), core)
    assert "moe_step_idle_share" not in aux


# The decode side's chosen experts leave LFM2's and Kimi's cells' programs
# alone: 128 tokens a step choosing 4 of 32 experts leave none idle, 64
# choosing 8 of 256 too few (0.131, under ``IDLE_SHARE_MIN``), so their
# steps and fragments lower, at the cells' shapes and from abstract
# arguments, to the text they had before that form came
CELL_TEXT = {
    "lfm2_moe_rl": (
        "5236ace8eb5b048780eb128f90aae333f33fe8ae9489c1c3205010796d0f5b3a",
        "5742341a41d94301e3ad0405df10dcf13f00170354ffd057cde13c6e421c7924"),
    "kimi_linear_rl": (
        "21d38cd5b880d98a9b156fec7be9473876e19072e52c76e7b592c514c89089b9",
        "87a034385f07ca9dade5ffbfc6fc15f16309ac4371eb2618b99a2f7468e44fb6"),
}


@pytest.mark.parametrize("preset", list(CELL_TEXT))
def test_the_cells_the_rule_leaves_lower_to_the_text_they_had(preset):
    """At the chip's tiles (``moe.TILE``), as the cells run."""
    cfg = presets.get(preset)
    model = networks.build_model(cfg, registry.make(cfg.env_id, cfg).spec)
    B, T = cfg.num_envs, cfg.unroll_len
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    core = jax.eval_shape(lambda: model.initial_core(B))
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    step = jax.jit(model.apply).lower(variables, ints(B), core).as_text()
    fragment = jax.jit(
        lambda v, o, d, c, a: model.apply(v, o, d, c, a, method="fragment")
    ).lower(variables, ints(T, B), jax.ShapeDtypeStruct((T, B), bool), core,
            ints(T, B)).as_text()
    for form, text, digest in zip(("step", "fragment"), (step, fragment),
                                  CELL_TEXT[preset]):
        assert hashlib.sha256(text.encode()).hexdigest() == digest, form
