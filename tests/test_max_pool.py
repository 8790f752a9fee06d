"""ops/max_pool.py: the Pallas kernels against ``nn.max_pool`` in the
interpreter, the choice between them and the fallback, and a compile for a
described v5e that pins what the ``[H, W, C, N]`` view is supposed to cost:
nothing."""

import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from asyncrl_tpu.models.networks import ImpalaCNN
from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import max_pool as mp
from asyncrl_tpu.ops.max_pool import max_pool_3x3_s2

SHAPES = [(84, 84, 16), (42, 42, 32), (21, 21, 32), (64, 64, 16), (11, 11, 3)]


def reference(x):
    return nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")


def kernel_value_and_vjp(x, g):
    y, pos = mp._kernel_fwd(x, interpret=True)
    return y, mp._kernel_bwd(pos, g, x.shape, interpret=True)


def sites():
    return introspect.process_record()["pool_sites"]


def sites_since(before):
    return {k: v - before[k] for k, v in sites().items()}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hwc", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_kernels_equal_nn_max_pool_with_ties_everywhere(hwc, dtype):
    # three integer values: nearly every window ties; integer cotangents,
    # so the float32 sums of up to four of them are exact in either dtype
    kx, kg = jax.random.split(jax.random.key(sum(hwc)))
    x = jax.random.randint(kx, (128, *hwc), -1, 2).astype(dtype)
    want_y, vjp = jax.vjp(reference, x)
    g = jax.random.randint(kg, want_y.shape, -3, 4).astype(dtype)
    y, dx = kernel_value_and_vjp(x, g)
    assert y.dtype == dx.dtype == dtype
    assert jnp.array_equal(y, want_y)
    assert jnp.array_equal(dx, vjp(g)[0])


def test_a_nine_way_tie_sends_the_gradient_to_the_first_element():
    x = jnp.ones((1, 128, 6, 6, 8), jnp.bfloat16)  # leading dims flatten
    want_y, vjp = jax.vjp(reference, x)
    g = jnp.arange(9, dtype=jnp.bfloat16).reshape(3, 3, 1) + jnp.ones_like(
        want_y)
    y, dx = kernel_value_and_vjp(x, g)
    assert jnp.array_equal(y, want_y)
    assert jnp.array_equal(dx, vjp(g)[0])
    assert jnp.count_nonzero(dx[0, 0, :, :, 0]) == 9
    assert dx[0, 0, 2, 4, 0] == g[0, 0, 1, 2, 0]  # the window's corner


def test_a_call_that_is_not_differentiated_is_reduce_window_max():
    x = jnp.zeros((128, 8, 8, 4), jnp.bfloat16)
    (call,) = jax.make_jaxpr(max_pool_3x3_s2)(x).eqns
    inner = call.params["call_jaxpr"]
    assert [e.primitive.name for e in inner.eqns] == ["reduce_window_max"]
    assert str(inner) == str(jax.make_jaxpr(reference)(x))
    before = sites()
    lowered = jax.jit(max_pool_3x3_s2).lower(x).as_text()
    assert "reduce_window" in lowered and "case" not in lowered
    assert sites_since(before) == {"kernel": 0, "fallback": 0}


@pytest.mark.parametrize("n", [128, 96], ids=["lanes_full", "lanes_ragged"])
def test_off_the_tpu_every_differentiated_site_falls_back(n):
    # n = 128 fits the kernels and is turned down when lowered for the CPU;
    # n = 96 (a grad_accum chunk, a PPO minibatch) when traced
    model = ImpalaCNN(channels=(4, 8, 8), compute_dtype=jnp.bfloat16)
    obs = jnp.zeros((2, n // 2, 16, 16, 4), jnp.uint8)
    params = model.init(jax.random.key(0), obs[0, :1])
    assert mp._kernel_fits((n, 16, 16, 4), jnp.bfloat16) == (n == 128)

    def loss(p):
        return jnp.sum(model.apply(p, obs).astype(jnp.float32))

    before = sites()
    text = jax.jit(jax.grad(loss)).lower(params).as_text()
    assert sites_since(before) == {"kernel": 0, "fallback": 3}
    assert text.count("stablehlo.select_and_scatter") == 3
    assert "tpu_custom_call" not in text


def test_gradients_under_vmap_equal_nn_max_pool():
    # PopulationTrainer differentiates under vmap
    x = jax.random.randint(jax.random.key(1), (3, 128, 6, 6, 8), -1, 2)
    x = x.astype(jnp.float32)

    def grad_of(pool):
        return jax.vmap(jax.grad(lambda v: jnp.sum(pool(v) ** 2)))(x)

    assert jnp.array_equal(grad_of(max_pool_3x3_s2), grad_of(reference))


@pytest.mark.parametrize("shape,dtype,fits", [
    ((33, 256, 84, 84, 16), jnp.bfloat16, True),
    ((8448, 11, 11, 3), jnp.float32, True),
    ((8, 256, 84, 84, 16), jnp.bfloat16, True),    # 2048 lanes
    ((33, 64, 84, 84, 16), jnp.bfloat16, False),   # 2112 = 16.5 x 128
    ((84, 84, 16), jnp.bfloat16, False),           # no batch
    ((128, 84, 84, 16), jnp.float16, False),
    ((128, 84, 84, 16), jnp.float32, False),       # 2 x 72 MB of blocks
    ((128, 256, 256, 32), jnp.bfloat16, False),
    ((0, 84, 84, 16), jnp.bfloat16, False),
], ids=str)
def test_the_shapes_the_kernels_take(shape, dtype, fits):
    assert mp._kernel_fits(shape, dtype) == fits


# ------------------------------------------- compiled for a described v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a deviceless compile can write the persistent cache, never read it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def test_the_learner_geometry_compiles_to_six_kernels_and_no_copy(one_chip):
    model = ImpalaCNN(compute_dtype=jnp.bfloat16)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 84, 84, 4))))
    params, obs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, jax.ShapeDtypeStruct((33, 256, 84, 84, 4), jnp.uint8)))

    def loss(p, o):
        return jnp.sum(model.apply(p, o).astype(jnp.float32) ** 2)

    before = sites()
    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, obs).compile().as_text()
    assert sites_since(before) == {"kernel": 3, "fallback": 0}
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]+)"', text)
    assert len(calls) == 6
    for i in range(3):
        assert sum(f"/section{i}/max_pool/" in c and "max_pool_fwd" in c
                   and "/jvp(" in c for c in calls) == 1
        assert sum(f"/section{i}/max_pool/" in c and "max_pool_bwd" in c
                   and "/transpose(jvp(" in c for c in calls) == 1
    assert "select-and-scatter" not in text
    # the [H, W, C, N] view is the layout XLA keeps: nothing the size of a
    # pre-pool activation, or of a pooled one, is copied or transposed (the
    # last pooled size is also the Dense layer's input, relaid out anyway)
    activations = {8448 * h * h * c for h, c in
                   [(84, 16), (42, 16), (42, 32), (21, 32)]}
    def size(dims):
        return math.prod(map(int, dims.split(",")))

    for dims in re.findall(
            r"= bf16\[([\d,]+)\]\S* (?:copy|transpose)\(", text):
        assert size(dims) not in activations, dims
    # and the barrier behind the forward call holds: without it the
    # residual blocks' x + f(x) is computed in both logical shapes, five
    # fusions write two activations each, and the backward ones read five
    # (with nn.max_pool, and with the barrier, one fusion writes two)
    twice = [outs for outs in re.findall(r"= \((.*?)\) fusion\(", text)
             if sum(size(d) in activations | {8448 * 11 * 11 * 32}
                    for d in re.findall(r"bf16\[([\d,]+)\]", outs)) >= 2]
    assert len(twice) <= 1, twice


# The other kernel of the main path compiled for the described chip lives in
# this file too: only one process may describe a chip, and the workers of a
# test run are given whole files.
def test_the_one_token_kda_step_compiles_to_one_in_place_kernel_under_its_scope(
        one_chip):
    """``kimi_linear_rl``'s KDA widths, one layer: the rollout's one-token
    form lowers the recurrence as the Mosaic kernel, under ``/kda/kda_step/``
    (``kda_step_device_ms``, ``kda_device_ms`` and ``kda_step_roofline``
    read that path), updating the state in place."""
    from asyncrl_tpu.models import kimi_linear

    shape = kimi_linear.SeqShape(
        hidden=256, vocab=512, layers=("kda+dense",),
        kda_heads=32, kda_head_dim=128,
        mla_heads=2, qk_nope=16, qk_rope=8, v_head=16, kv_lora=24,
        dense_ffn=256, expert_ffn=32, num_experts=8, held_experts=(0,),
        top_k=2, routed_scale=1.0, max_positions=32,
    )
    model = kimi_linear.SeqPolicy(shape, compute_dtype=jnp.bfloat16)
    B = 64  # 537 MB of state: XLA cannot park it in VMEM around the call
    variables, core = jax.eval_shape(
        lambda: (model.init(jax.random.key(0)), model.initial_core(B)))
    variables, tokens, core = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (variables, jax.ShapeDtypeStruct((B,), jnp.int32), core))

    def sites():
        return introspect.process_record()["kda_sites"]

    before = sites()
    text = jax.jit(model.apply, donate_argnums=2).lower(
        variables, tokens, core).compile().as_text()
    assert {k: v - before[k] for k, v in sites().items()} == {
        "step": 0, "step_kernel": 1, "chunk": 0, "pair": 0, "pair_kernel": 0}
    calls = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 1
    (call,) = calls
    assert re.search(r'op_name="[^"]*/kda/kda_step/[^"]*pallas_call', call), call
    # the state's operand is the state's result
    assert "output_to_operand_aliasing" in call, call
    # and nothing else passes over it: no copy, no select the size of the state
    state = f"f32[{B},32,128,128]"
    passes = re.findall(rf"= {re.escape(state)}\S* (\S+?)\(", text)
    assert "get-tuple-element" in passes  # the kernel's own result
    assert set(passes) <= {"parameter", "get-tuple-element", "bitcast"}, passes


def test_the_chunked_kda_compiles_its_sub_chunk_pairs_to_kernels_under_its_scope(
        one_chip):
    """``kimi_linear_rl``'s KDA widths, one layer's chunked scan over a block
    of envs, differentiated as the learner does: the pairs inside a
    sub-chunk lower as the Mosaic kernels, forward and backward, under
    ``/kda_chunk/`` (``kda_chunk_device_ms`` and ``kda_device_ms`` read that
    path), and no ``[.., 16, 16, 128]`` operand is left in the program."""
    from asyncrl_tpu.ops import kda

    T, B, H, d = 256, 16, 32, 128
    shapes = dict(S0=(B, H, d, d), q=(T, B, H, d), k=(T, B, H, d),
                  v=(T, B, H, d), g=(T, B, H, d), beta=(T, B, H))
    args = {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for n, s in shapes.items()}
    done = jax.ShapeDtypeStruct((T, B), jnp.bool_, sharding=one_chip)

    def loss(args, done):
        with jax.named_scope("kda"):  # as the mixer does
            S, o = kda.kda_chunk(*(args[n] for n in shapes), done,
                                 dtype=jnp.bfloat16)
        return jnp.sum(o) + jnp.sum(S)

    def sites():
        return introspect.process_record()["kda_sites"]

    before = sites()
    text = jax.jit(jax.value_and_grad(jax.checkpoint(loss))).lower(
        args, done).compile().as_text()
    since = {k: v - before[k] for k, v in sites().items()}
    # a site a lowering: the primal pass, the outer checkpoint's and the scan
    # body's own rematerialised forward, the backward
    assert since == {"step": 0, "step_kernel": 0, "chunk": 1, "pair": 0,
                     "pair_kernel": 4}
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]+)"', text)
    assert len(calls) == 4
    for call in calls:
        assert "/kda_chunk/" in call, call
    assert sum(c.endswith("kda_pairs_fwd/pallas_call") for c in calls) == 3
    assert sum(c.endswith("kda_pairs_bwd/pallas_call") and "transpose(" in c
               for c in calls) == 1
    assert not re.findall(r"f32\[[\d,]*16,16,128\]", text)


def assert_the_cache_is_left_in_place(text, cache):
    """In a compiled decode loop nothing passes over an array of the
    ``cache``'s shape (a regex) but the row's write, in place, and the
    kernel, and the fusions that give one back are the scatters of one
    row."""
    passes = re.findall(rf"= {cache}\S* (\S+?)\(", text)
    assert "fusion" in passes or "scatter" in passes  # the write
    assert set(passes) <= {"parameter", "get-tuple-element", "bitcast",
                           "fusion", "scatter"}, passes
    for body in re.findall(
            rf"\n(%fused_computation\S*) \([^\n]*\) -> {cache} \{{(.*?)\n\}}", text,
            flags=re.S):
        assert "scatter(" in body[1], body[0]


def test_the_one_token_attention_compiles_to_one_kernel_that_leaves_the_cache_in_place(
        one_chip):
    """``lfm2_moe_rl``'s attention widths and cache, one layer, a scan of
    one-token steps as the rollout runs them: ``ops/gqa.py``'s Mosaic kernel
    under ``/gqa/gqa_step/`` (``gqa_device_ms`` reads that path, and a
    reader can select the kernel by its name), and nothing in the loop's
    body copies a ``[128, 2048, 512]`` array or lays it out again: the
    kernel's operands are the arrays the row's write leaves (PERF.md, PR 30:
    a copy of the cache a token cost a third of the rollout)."""
    from asyncrl_tpu.models import lfm2_moe

    shape = lfm2_moe.Lfm2Shape(
        hidden=256, vocab=512, layers=("gqa+moe",),
        heads=32, kv_heads=8, head_dim=64, rope_theta=1e6,
        dense_ffn=256, expert_ffn=32, num_experts=8, held_experts=(0, 1),
        top_k=2, routed_scale=1.0, max_positions=2048,
    )
    model = lfm2_moe.Lfm2Policy(shape, compute_dtype=jnp.bfloat16)
    B, T = 128, 4
    variables, core = jax.eval_shape(
        lambda: (model.init(jax.random.key(0)), model.initial_core(B)))
    variables, tokens, core = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (variables, jax.ShapeDtypeStruct((T, B), jnp.int32), core))

    def rollout(variables, tokens, core):
        def step(core, token):
            logits, value, core = model.apply(variables, token, core)
            return core, (jnp.argmax(logits, axis=-1), value)
        return jax.lax.scan(step, core, tokens)

    def gqa_sites():
        return introspect.process_record()["gqa_sites"]

    before = gqa_sites()
    text = jax.jit(rollout, donate_argnums=2).lower(
        variables, tokens, core).compile().as_text()
    assert {k: v - before[k] for k, v in gqa_sites().items()} == {
        "step": 0, "step_kernel": 1}
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 1
    (call,) = calls
    assert re.search(r'op_name="[^"]*/gqa/gqa_step/[^"]*pallas_call', call), call
    assert re.search(r"%gqa_step\S* = \S+ custom-call\(", text)  # the kernel's name
    assert_the_cache_is_left_in_place(text, rf"bf16\[{B},2048,512\]")
    # rows beyond len stay in HBM: no product over the capacity is left
    assert not re.findall(rf"f32\[{B},32,2048\]", text)


def test_the_one_token_sparse_attention_compiles_to_the_kernel_under_the_selections_mask(
        one_chip):
    """``keye_moe_rl``'s attention and indexer widths and cache, one layer, a
    scan of one-token steps as the rollout runs them: index and ``select``
    over the capacity, then ``ops/gqa.py``'s Mosaic kernel under
    ``/gqa/dsa_attend/`` (``dsa_attend_device_ms`` and ``keye_gqa_device_ms``
    read that path), counted by ``dsa_sites``; no product over the cache's
    8,192-row capacity is left, and nothing in the loop's body copies a
    ``[16, 8192, 512]`` array or lays it out again."""
    from asyncrl_tpu.models import keye_moe

    shape = keye_moe.KeyeShape(
        hidden=256, vocab=512, layers=("dsa+moe",),
        heads=32, kv_heads=4, head_dim=128, rope_theta=1e7,
        index_heads=16, index_dim=64, index_top_k=2048,
        expert_ffn=32, num_experts=8, held_experts=(0, 1),
        top_k=2, routed_scale=1.0, max_positions=8192,
    )
    model = keye_moe.KeyePolicy(shape, compute_dtype=jnp.bfloat16)
    B, T = 16, 4
    variables, core = jax.eval_shape(
        lambda: (model.init(jax.random.key(0)), model.initial_core(B)))
    variables, tokens, core = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (variables, jax.ShapeDtypeStruct((T, B), jnp.int32), core))

    def rollout(variables, tokens, core):
        def step(core, token):
            logits, value, core = model.apply(variables, token, core)
            return core, (jnp.argmax(logits, axis=-1), value)
        return jax.lax.scan(step, core, tokens)

    def sites(name):
        return introspect.process_record()[name]

    before = {name: sites(name) for name in ("dsa_sites", "gqa_sites")}
    text = jax.jit(rollout, donate_argnums=2).lower(
        variables, tokens, core).compile().as_text()
    assert {k: v - before["dsa_sites"][k] for k, v in sites("dsa_sites").items()} == {
        "step": 0, "step_kernel": 1}
    assert sites("gqa_sites") == before["gqa_sites"]  # ``gqa_step``'s own calls
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 1
    (call,) = calls
    assert re.search(r'op_name="[^"]*/gqa/dsa_attend/[^"]*pallas_call', call), call
    assert re.search(r"%gqa_step\S* = \S+ custom-call\(", text)  # the kernel's name
    assert_the_cache_is_left_in_place(text, rf"bf16\[{B},8192,512\]")
    # rows beyond len stay in HBM: the heads' scores over the capacity are gone
    assert not re.findall(rf"f32\[{B},32,8192\]", text)


def test_the_one_token_latent_attention_compiles_to_one_kernel_that_reads_the_cache_in_place(
        one_chip):
    """``moonlight_rl``'s attention widths and cache, one layer, a scan of
    one-token steps as the rollout runs them: ``ops/latent.py``'s Mosaic
    kernel named ``mla_step`` under ``/mla/mla_step/``, counted by
    ``mla_sites``; no product over the cache's 8,192-row capacity is left,
    and in the loop's body nothing copies a ``[16, 8192, 576]`` array or
    lays it out again (the program's edges lay the donated cache out for
    the loop and back, as the whole step does at the parent: its state
    comes in with the 8,192 rows minor)."""
    from asyncrl_tpu.models import moonlight

    shape = moonlight.MoonlightShape(
        hidden=256, vocab=512, layers=("mla+dense",),
        mla_heads=16, qk_nope=128, qk_rope=64, v_head=128, kv_lora=512,
        rope_theta=50000.0, dense_ffn=256, expert_ffn=32, shared_ffn=64,
        num_experts=8, held_experts=(0, 1), top_k=2, routed_scale=2.446,
        max_positions=8192)
    model = moonlight.MoonlightPolicy(shape, compute_dtype=jnp.bfloat16)
    B, T = 16, 4
    variables, core = jax.eval_shape(
        lambda: (model.init(jax.random.key(0)), model.initial_core(B)))
    variables, tokens, core = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (variables, jax.ShapeDtypeStruct((T, B), jnp.int32), core))

    def rollout(variables, tokens, core):
        def step(core, token):
            logits, value, core = model.apply(variables, token, core)
            return core, (jnp.argmax(logits, axis=-1), value)
        return jax.lax.scan(step, core, tokens)

    def mla_sites():
        return introspect.process_record()["mla_sites"]

    before = mla_sites()
    text = jax.jit(rollout, donate_argnums=2).lower(
        variables, tokens, core).compile().as_text()
    assert {k: v - before[k] for k, v in mla_sites().items()} == {
        "step": 0, "step_kernel": 1}
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 1
    (call,) = calls
    assert re.search(r'op_name="[^"]*/mla/mla_step/[^"]*pallas_call', call), call
    assert re.search(r"%mla_step\S* = \S+ custom-call\(", text)  # the kernel's name
    (body,) = [c for c in re.split(r"\n\n", text) if re.search(r"%mla_step\S* = ", c)]
    assert_the_cache_is_left_in_place(body, rf"bf16\[{B},8192,576\]")
    # rows beyond len stay in HBM: the heads' scores over the capacity are gone
    assert not re.findall(rf"f32\[{B},16,8192\]", text)


def test_the_expert_layers_decode_step_compiles_to_one_kernel_over_the_chosen_experts(
        one_chip):
    """``keye_moe_rl``'s expert layer (16 of 128 experts held, D 2,048, F
    768, float32 weights cast to bfloat16 as the policy's are), a scan of
    decode steps of 16 tokens as the rollout runs them: ``ops/moe.py
    held_experts`` takes the Mosaic kernel ``moe_chosen`` under
    ``/moe_dense/``, counted ``"dense_skip"`` by ``moe_sites``; its weights
    come in as one stacked operand formed outside the loop, and in the
    loop's body nothing prefetches, converts or stacks an expert's weights
    (XLA prefetches an operand that fits VMEM whole: every expert)."""
    from asyncrl_tpu.ops import moe

    T, N, D, F, E, held = 4, 16, 2048, 768, 128, tuple(range(16))
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    args = (sds(T, N, D), sds(D, E), sds(len(held), D, F), sds(len(held), D, F),
            sds(len(held), F, D))

    def rollout(xs, router, gate, up, down):
        def step(carry, x):
            ids, weights = moe.route(x, router, None, 8, 1.0)
            y, _, _ = moe.held_experts(x, ids, weights, held, E, gate, up, down,
                                       jnp.bfloat16)
            return carry + y, None
        return jax.lax.scan(step, jnp.zeros((N, D)), xs)[0]

    def moe_sites():
        return introspect.process_record()["moe_sites"]

    before = moe_sites()
    text = jax.jit(rollout).lower(*args).compile().as_text()
    assert {k: v - before[k] for k, v in moe_sites().items()} == {
        "grouped": 0, "gathered": 0, "dense": 0, "dense_skip": 1}
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*/moe_dense/[^"]*moe_chosen/pallas_call', calls[0])
    (body,) = [c for c in re.split(r"\n\n", text) if re.search(r"%moe_chosen\S* = ", c)]
    weights = (rf"\[16,{D},{F}\]", rf"\[16,{F},{D}\]", rf"\[16,3,{D},{F}\]")
    for line in body.split("\n"):
        if re.search(r"(slice|copy)-start\(|convert\(|fusion\(", line):
            result = line.split("=", 2)[1][:48]
            assert not any(re.search(w, result) for w in weights), line
    # no expert's activations for every held expert: the dense lines are gone
    assert not re.findall(rf"f32\[16,{N},{F}\]", text)
