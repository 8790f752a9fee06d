"""Attention that chooses its rows (ops/dsa.py): the exact selection against
``lax.top_k`` with ties, the one-token form and the fragment form against
plain lines written here (a top-k by ``lax.top_k``, a softmax over the chosen
rows a query at a time), the KL term, where its gradient goes and where it
does not, the form that serves a cache no longer than ``top_k``, and the
forms that attend the chosen rows of a deeper one: ``ops/gqa.py``'s kernel
under the selection's mask (in the Pallas interpreter, the platform's choice
forced) or the masked products, its VJP, and the counter of the choice; and
the fragment form over each env's rung of the cache against the all-rows
form, what its VJP keeps, and the rows it counts."""

import contextlib
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import dsa, gqa

H, G, DH, J, DI = 4, 2, 8, 3, 4
SCALE = DI ** -0.5 * J ** -0.5


def top_k_mask(scores, valid, k):
    """The plain lines: ``lax.top_k`` (descending, ties to the lower index)
    of the valid rows, as a mask. -0.0 and +0.0 are one score (``lax.top_k``
    sorts by the total order, in which they are two)."""
    masked = jnp.where(valid, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, rows = lax.top_k(masked, min(k, scores.shape[-1]))
    hit = jnp.zeros(scores.shape, bool)
    hit = jnp.put_along_axis(hit, rows, True, axis=-1, inplace=False)
    return hit & valid


def scores_case(case, shape, key):
    x = jax.random.normal(key, shape)
    if case == "ties":  # a few distinct values: most rows tie with others
        return jnp.round(x)
    if case == "all_equal":
        return jnp.zeros(shape)
    if case == "signed_zeros":  # -0.0 and +0.0 are one score
        return jnp.where(x > 0, 0.0, -0.0) * jnp.abs(x)
    if case == "huge":  # the whole float32 range, infinities too
        return jnp.where(x > 1, jnp.inf, jnp.where(x < -1, -jnp.inf, x * 1e38))
    return x


@pytest.mark.parametrize("k", [1, 5, 16, 40])
@pytest.mark.parametrize("case", ["random", "ties", "all_equal", "signed_zeros", "huge"])
def test_select_is_an_exact_top_k_with_ties_to_the_lower_index(case, k):
    key = jax.random.PRNGKey(k)
    scores = scores_case(case, (6, 33), key)
    # rows 0..n of each query are valid: 1, 2, 5, 16, 17 and all 33
    valid = jnp.arange(33)[None, :] < jnp.asarray([1, 2, 5, 16, 17, 33])[:, None]
    chosen = jax.jit(dsa.select, static_argnums=2)(scores, valid, k)
    np.testing.assert_array_equal(chosen, top_k_mask(scores, valid, k))
    np.testing.assert_array_equal(
        jnp.sum(chosen, axis=-1), jnp.minimum(jnp.sum(valid, axis=-1), k))


def operands(B, L, key, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    normal = lambda k, *shape: jax.random.normal(k, shape)
    return dict(
        keys=normal(ks[0], B, L, G * DH).astype(dtype),
        values=normal(ks[1], B, L, G * DH).astype(dtype),
        ki=normal(ks[2], B, L, DI).astype(dtype),
    )


def plain_attend(q, qi, w, keys, values, ki, valid, k):
    """One env's queries [Q, ...] over its rows [P, ...]: the definition."""
    products = jnp.einsum("qjd,pd->qjp", qi, ki.astype(jnp.float32))
    index = SCALE * jnp.einsum("qj,qjp->qp", w, jax.nn.relu(products))
    chosen = top_k_mask(index, valid, k)
    heads, dh = q.shape[-2:]
    keys, values = (a.astype(jnp.float32).reshape(a.shape[0], -1, dh)
                    for a in (keys, values))
    keys, values = (jnp.repeat(a, heads // a.shape[1], axis=1) for a in (keys, values))
    attn = jnp.einsum("qhd,phd->hqp", q, keys) / np.sqrt(dh)
    probs = jax.nn.softmax(jnp.where(chosen[None], attn, -jnp.inf), axis=-1)
    out = jnp.einsum("hqp,phd->qhd", probs, values)
    target = jnp.mean(probs, axis=0)
    log_pi = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(
        chosen & (target > 0),
        target * (jnp.log(jnp.where(target > 0, target, 1.0)) - log_pi), 0.0))
    return out, kl, chosen


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_one_token_form_attends_the_top_k_rows_up_to_len(dtype):
    B, L, k = 5, 24, 6
    rows = operands(B, L, jax.random.PRNGKey(0), dtype)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, DH))
    qi = jax.random.normal(ks[1], (B, J, DI))
    w = jax.random.normal(ks[2], (B, J))
    length = jnp.asarray([0, 3, 5, 6, 23])  # fewer rows than k, k, more
    out = jax.jit(dsa.dsa_step, static_argnums=(7, 8))(
        q, rows["keys"], rows["values"], qi, w, rows["ki"], length, k, SCALE)
    for b in range(B):
        valid = (jnp.arange(L) <= length[b])[None]
        ref, _, chosen = plain_attend(
            q[b][None], qi[b][None].astype(dtype).astype(jnp.float32), w[b][None],
            rows["keys"][b], rows["values"][b], rows["ki"][b], valid, k)
        assert int(jnp.sum(chosen)) == min(int(length[b]) + 1, k)
        np.testing.assert_allclose(
            out[b], ref[0], atol=2e-5 if dtype == jnp.float32 else 3e-2)


def test_a_cache_no_longer_than_top_k_is_served_by_gqa_step():
    B, L = 3, 8
    rows = operands(B, L, jax.random.PRNGKey(2))
    q = jax.random.normal(jax.random.PRNGKey(3), (B, H, DH))
    qi, w = jnp.zeros((B, J, DI)), jnp.zeros((B, J))
    length = jnp.asarray([0, 4, 7])
    before = introspect.process_record()["gqa_sites"]
    out = dsa.dsa_step(q, rows["keys"], rows["values"], qi, w, rows["ki"], length,
                       L, SCALE)
    after = introspect.process_record()["gqa_sites"]
    assert after["step"] == before["step"] + 1
    for b in range(B):
        ref, _, _ = plain_attend(
            q[b][None], qi[b][None], w[b][None], rows["keys"][b], rows["values"][b],
            rows["ki"][b], (jnp.arange(L) <= length[b])[None], L)
        np.testing.assert_allclose(out[b], ref[0], atol=2e-5)


# ---- a cache deeper than top_k at a shape ``ops/gqa.py``'s kernel takes

KC = gqa.CHUNK
KL, KH, KG, KDH, KTOP = 4 * KC, 8, 2, 64, KC + 44  # rows of 128 lanes


def kernel_case(dtype=jnp.float32, lengths=(0, 17, KTOP - 2, KTOP - 1, KTOP, 2 * KC, KL - 1)):
    """Lengths below, at and above ``top_k`` (``len`` + 1 rows are scored)."""
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape)
    return dict(
        q=normal(ks[0], B, KH, KDH), keys=normal(ks[1], B, KL, KG * KDH).astype(dtype),
        values=normal(ks[2], B, KL, KG * KDH).astype(dtype), qi=normal(ks[3], B, J, DI),
        w=normal(ks[4], B, J), ki=normal(ks[5], B, KL, DI).astype(dtype),
        length=jnp.asarray(lengths, jnp.int32))


def step_of(case):
    return dsa.dsa_step(*(case[n] for n in ("q", "keys", "values", "qi", "w", "ki", "length")),
                        KTOP, SCALE)


@contextlib.contextmanager
def on_a_tpu():
    """What a program lowered for a TPU takes of ``lax.platform_dependent``,
    with the kernel in the Pallas interpreter."""
    with mock.patch.object(lax, "platform_dependent",
                           lambda *args, tpu, default: tpu(*args)), \
            mock.patch.object(gqa, "_kernel_step",
                              functools.partial(gqa._kernel_step, interpret=True)):
        yield


def dsa_sites_since(before):
    now = introspect.process_record()["dsa_sites"]
    return {k: now[k] - before[k] for k in now}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_one_token_form_by_the_kernel_is_the_definition(dtype):
    """``dsa_step`` where the kernel serves: index and ``select`` over the
    capacity, then the kernel under the selection's mask, against the
    definition a query at a time; and the masked products give the same."""
    case = kernel_case(dtype)
    assert gqa._kernel_fits(case["q"].shape, case["keys"].shape, dtype, masked=True)
    before = introspect.process_record()["dsa_sites"]
    with on_a_tpu():
        out = jax.jit(step_of)(case)
    assert dsa_sites_since(before) == {"step_kernel": 1, "step": 0}
    plain = jax.jit(lambda case: step_of(case))(case)  # on the CPU: the masked products
    assert dsa_sites_since(before) == {"step_kernel": 1, "step": 1}
    assert bool(jnp.all(jnp.isfinite(out)))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, plain, atol=tol)
    for b, n in enumerate(case["length"]):
        ref, _, chosen = plain_attend(
            case["q"][b][None], case["qi"][b][None].astype(dtype).astype(jnp.float32),
            case["w"][b][None], case["keys"][b], case["values"][b], case["ki"][b],
            (jnp.arange(KL) <= n)[None], KTOP)
        assert int(jnp.sum(chosen)) == min(int(n) + 1, KTOP)
        np.testing.assert_allclose(out[b], ref[0], atol=tol)


def test_the_kernels_vjp_under_the_mask_is_the_masked_products():
    """What a differentiated call on a TPU runs (the learner's bootstrap
    token): the kernel forward, the plain lines' backward; neither the
    selection nor ``len`` takes a cotangent, and no row that was not chosen
    a gradient."""
    case = kernel_case()
    length = case["length"]
    chosen = dsa.select(
        dsa.index_scores(case["qi"][:, None], case["w"][:, None], case["ki"], SCALE)[:, 0],
        jnp.arange(KL)[None, :] <= length[:, None], KTOP)
    mix = jax.random.normal(jax.random.PRNGKey(7), case["q"].shape)
    operands = (case["q"], case["keys"], case["values"])
    with on_a_tpu():
        value, mine = jax.value_and_grad(
            lambda *o: jnp.sum(dsa._kernel_attend(*o, length, chosen) * mix),
            argnums=range(3))(*operands)
    ref_value, ref = jax.value_and_grad(
        lambda *o: jnp.sum(dsa._attend_rows(*o, chosen) * mix), argnums=range(3))(*operands)
    np.testing.assert_allclose(value, ref_value, rtol=1e-5)
    for a, b in zip(mine, ref):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)
    assert float(jnp.max(jnp.abs(jnp.where(chosen[..., None], 0.0, mine[1])))) == 0.0
    assert float(jnp.max(jnp.abs(mine[1]))) > 0.0
    # and through dsa_step, as the model calls it
    loss = lambda q, keys, values: jnp.sum(step_of(
        {**case, "q": q, "keys": keys, "values": values}) * mix)
    with on_a_tpu():
        through = jax.grad(loss, argnums=range(3))(*operands)
    for a, b in zip(through, ref):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


@pytest.mark.parametrize("fits, differentiated", [
    (False, False), (False, True), (True, False), (True, True)])
def test_off_the_tpu_and_at_small_shapes_the_chosen_rows_are_attended_by_the_masked_products(
        fits, differentiated):
    """The other branch: by shape when the call is traced (rows of 16
    lanes), by platform when it is lowered (a shape the kernel takes, here
    on a CPU), differentiated or not: ``_attend_rows``' values and
    gradients, counted as ``"step"`` once per site and program lowered."""
    if fits:
        case = kernel_case()
    else:
        B, L, k = 5, 24, 6
        rows = operands(B, L, jax.random.PRNGKey(0))
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        case = dict(rows, q=jax.random.normal(ks[0], (B, H, DH)),
                    qi=jax.random.normal(ks[1], (B, J, DI)),
                    w=jax.random.normal(ks[2], (B, J)),
                    length=jnp.asarray([0, 3, 5, 6, 23]))
    k = KTOP if fits else 6
    assert gqa._kernel_fits(
        case["q"].shape, case["keys"].shape, jnp.float32, masked=True) == fits
    L = case["keys"].shape[1]

    def by(attend):
        def f(q, keys, values):
            chosen = dsa.select(
                dsa.index_scores(case["qi"][:, None], case["w"][:, None], case["ki"],
                                 SCALE)[:, 0],
                jnp.arange(L)[None, :] <= case["length"][:, None], k)
            return attend(q, keys, values, chosen)
        return f

    module = lambda q, keys, values: dsa.dsa_step(
        q, keys, values, case["qi"], case["w"], case["ki"], case["length"], k, SCALE)
    wrap = (lambda f: jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=range(3))) \
        if differentiated else (lambda f: lambda *a: (f(*a),))
    args = (case["q"], case["keys"], case["values"])
    before = introspect.process_record()["dsa_sites"]
    step = jax.jit(wrap(module))
    mine = step(*args)
    assert dsa_sites_since(before) == {"step": 1, "step_kernel": 0}
    step(*args)  # a steady call counts nothing
    assert dsa_sites_since(before) == {"step": 1, "step_kernel": 0}
    for a, b in zip(mine, jax.jit(wrap(by(dsa._attend_rows)))(*args)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


def test_dsa_sites_count_every_site_of_a_program():
    """Two sites of one shape in one program are two (the site's lowering is
    not cached), a second program of the same function counts again, and a
    program lowered for a TPU counts the kernel."""
    case = kernel_case(lengths=(0, KTOP + 5))

    def two_sites(q):
        return step_of({**case, "q": step_of({**case, "q": q})})

    before = introspect.process_record()["dsa_sites"]
    jax.jit(two_sites)(case["q"])
    assert dsa_sites_since(before) == {"step": 2, "step_kernel": 0}
    jax.jit(lambda q: two_sites(q))(case["q"])
    assert dsa_sites_since(before) == {"step": 4, "step_kernel": 0}
    before_gqa = introspect.process_record()["gqa_sites"]
    with on_a_tpu():
        jax.jit(lambda q: two_sites(q) + 0)(case["q"])
    assert dsa_sites_since(before) == {"step": 4, "step_kernel": 2}
    # the sites are dsa's own: ``gqa_sites`` counts calls of ``gqa_step``
    assert introspect.process_record()["gqa_sites"] == before_gqa


def fragment_case():
    B, T, P, k = 2, 8, 20, 5
    rows = operands(B, P, jax.random.PRNGKey(4))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, T, H, DH))
    qi = jax.random.normal(ks[1], (B, T, J, DI))
    w = jax.random.normal(ks[2], (B, T, J))
    # env 0: 12 cached rows then its queries, causal; env 1: a boundary after
    # its third query, so its later queries see the fragment's rows only
    t = jnp.arange(T)
    cached = jnp.stack([jnp.arange(12) < 12, jnp.arange(12) < 7])  # [B, 12]
    episode = jnp.stack([jnp.zeros(T, int), (t >= 3).astype(int)])
    own = (t[None, :, None] >= t[None, None, :]) & (
        episode[:, :, None] == episode[:, None, :])
    mask = jnp.concatenate(
        [cached[:, None, :] & (episode == 0)[:, :, None], own], axis=-1)
    return (q, qi, w, mask, rows["keys"], rows["values"], rows["ki"]), k


def test_the_fragment_form_is_the_definition_a_query_at_a_time():
    args, k = fragment_case()
    out, counted = jax.jit(
        lambda *a: dsa.dsa_fragment(*a, k, SCALE, 4, True))(*args)
    q, qi, w, mask, keys, values, ki = args
    total = 0.0
    for b in range(q.shape[0]):
        ref, kl, chosen = plain_attend(
            q[b], qi[b], w[b], keys[b], values[b], ki[b], mask[b], k)
        np.testing.assert_allclose(out[b], ref, atol=2e-5)
        np.testing.assert_array_equal(counted["chosen"][b], chosen)
        total += kl
    assert float(counted["indexer_kl"]) == pytest.approx(float(total), rel=1e-5)
    scored = jnp.sum(mask, axis=-1)
    assert float(counted["dsa_rows_scored"]) == float(jnp.sum(scored))
    assert float(counted["dsa_rows_selected"]) == float(
        jnp.sum(jnp.minimum(scored, k)))
    assert float(counted["dsa_pruned_share"]) == float(jnp.sum(scored > k))
    assert 0 < float(counted["dsa_pruned_share"]) < scored.size


def test_the_kl_term_trains_the_indexer_and_nothing_else():
    """The heads' outputs pass no gradient to the indexer's operands (the
    selection is a constant); the KL term passes none to the heads' (its
    target is one)."""
    args, k = fragment_case()

    def both(*a):
        out, counted = dsa.dsa_fragment(*a, k, SCALE, 4)
        return jnp.sum(jnp.square(out)), counted["indexer_kl"]

    heads = jax.grad(lambda *a: both(*a)[0], argnums=(0, 1, 2, 4, 5, 6))(*args)
    index = jax.grad(lambda *a: both(*a)[1], argnums=(0, 1, 2, 4, 5, 6))(*args)
    norm = lambda g: float(jnp.linalg.norm(g.astype(jnp.float32)))
    q, qi, w, keys, values, ki = heads
    assert min(norm(q), norm(keys), norm(values)) > 0
    assert max(norm(qi), norm(w), norm(ki)) == 0
    q, qi, w, keys, values, ki = index
    assert max(norm(q), norm(keys), norm(values)) == 0
    assert min(norm(qi), norm(w), norm(ki)) > 0

    # and the gradient is the definition's
    def plain_kl(qi, w, ki):
        q, _, _, mask, keys, values, _ = args
        return sum(plain_attend(q[b], qi[b], w[b], keys[b], values[b], ki[b],
                                mask[b], k)[1] for b in range(q.shape[0]))

    def program_kl(qi, w, ki):
        q, _, _, mask, keys, values, _ = args
        return dsa.dsa_fragment(q, qi, w, mask, keys, values, ki, k, SCALE, 4)[1][
            "indexer_kl"]

    _, qi, w, _, _, _, ki = args
    mine = jax.grad(program_kl, argnums=(0, 1, 2))(qi, w, ki)
    ref = jax.grad(plain_kl, argnums=(0, 1, 2))(qi, w, ki)
    for a, b in zip(mine, ref):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


# ---- the fragment form over each env's rung of the cache (ISSUE 37)

RL, RT, RQ, RK = 32, 16, 8, 8  # keye_moe_tiny's cache, fragment and top-k
# each env's cached rows and the rung its blocks run over (an eighth, a
# quarter, a half or all of the 32): none, a rung's edge, one row past it,
# the whole cache; and an episode that ends after the fragment's sixth
# query, whose later queries see the fragment's rows only
LADDER_CASES = {
    "empty": ([0], [4], None), "edge": ([8], [8], None),
    "past_edge": ([9], [16], None), "full": ([32], [32], None),
    "boundary": ([13], [16], 6),
    "mixed": ([0, 8, 9, 32, 13], [4, 8, 16, 32, 16], 6),
}


def ladder_case(lengths, boundary, L=RL, T=RT):
    """Operands of ``len(lengths)`` envs over ``L`` cached rows and ``T`` of
    the fragment; with ``boundary`` the last env's episode ends after its
    query ``boundary - 1``."""
    B = len(lengths)
    rows = operands(B, L + T, jax.random.PRNGKey(8))
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    t = jnp.arange(T)
    episode = jnp.zeros((B, T), int)
    if boundary is not None:
        episode = episode.at[-1].set((t >= boundary).astype(int))
    cached = jnp.arange(L)[None, :] < jnp.asarray(lengths)[:, None]
    own = (t[None, :, None] >= t[None, None, :]) & (
        episode[:, :, None] == episode[:, None, :])
    mask = jnp.concatenate(
        [cached[:, None, :] & (episode == 0)[:, :, None], own], axis=-1)
    return (jax.random.normal(ks[0], (B, T, H, DH)),
            jax.random.normal(ks[1], (B, T, J, DI)),
            jax.random.normal(ks[2], (B, T, J)), mask,
            rows["keys"], rows["values"], rows["ki"])


def all_rows(q, qi, w, mask, keys, values, ki, with_chosen=False, tq=RQ):
    """The form before the ladder: every env's blocks over all ``L + T``
    rows -> (out, [kl, scored, selected, pruned] summed, chosen)."""
    T = q.shape[1]

    def env(args):
        q, qi, w, mask, keys, values, ki = args
        blocks = lambda a: a.reshape(T // tq, tq, *a.shape[1:])
        out, counted, *chosen = lax.map(
            jax.checkpoint(lambda xs: dsa._block(
                *xs, keys, values, ki, RK, SCALE, G, with_chosen)),
            tuple(blocks(a) for a in (q, qi, w, mask)))
        return (out.reshape(T, H, DH), jnp.sum(counted, axis=0),
                *(c.reshape(T, -1) for c in chosen))

    out, counted, *chosen = lax.map(env, (q, qi, w, mask, keys, values, ki))
    return out, jnp.sum(counted, axis=0), *chosen


@pytest.mark.parametrize("case", list(LADDER_CASES))
def test_the_fragment_form_over_its_rung_is_the_all_rows_form(case):
    """Each env's blocks run over its cached rows rounded up to a rung of the
    cache, and the fragment's: the outputs, the counters, the selection to
    the row and the gradients of every operand are the all-rows form's, and
    ``dsa_rows_computed`` counts the rung's rows."""
    lengths, rung, boundary = LADDER_CASES[case]
    args = ladder_case(lengths, boundary)
    assert dsa._rungs(RL, RT) == (4, 8, 16, 32)
    out, counted = jax.jit(
        lambda *a: dsa.dsa_fragment(*a, RK, SCALE, RQ, True))(*args)
    ref_out, ref_counted, ref_chosen = jax.jit(
        lambda *a: all_rows(*a, with_chosen=True))(*args)
    np.testing.assert_allclose(out, ref_out, atol=2e-6)
    np.testing.assert_array_equal(counted["chosen"], ref_chosen)
    kl, scored, selected, pruned = ref_counted
    assert float(counted["indexer_kl"]) == pytest.approx(float(kl), rel=1e-5)
    for name, want in (("dsa_rows_scored", scored), ("dsa_rows_selected", selected),
                       ("dsa_pruned_share", pruned)):
        assert float(counted[name]) == float(want), name
    assert float(counted["dsa_rows_computed"]) == RT * sum(c + RT for c in rung)

    mix = jax.random.normal(jax.random.PRNGKey(10), out.shape)
    q, qi, w, mask, keys, values, ki = args

    def loss(form):
        def f(q, qi, w, keys, values, ki):
            out, kl = form(q, qi, w, mask, keys, values, ki)
            return jnp.sum(out * mix) + kl
        return jax.jit(jax.grad(f, argnums=range(6)))

    mine = loss(lambda *a: (lambda o, c: (o, c["indexer_kl"]))(
        *dsa.dsa_fragment(*a, RK, SCALE, RQ)))(q, qi, w, keys, values, ki)
    ref = loss(lambda *a: (lambda o, c: (o, c[0]))(*all_rows(*a)))(
        q, qi, w, keys, values, ki)
    for name, a, b in zip(("q", "qi", "w", "keys", "values", "ki"), mine, ref):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7, err_msg=name)


def test_the_ladders_residuals_are_the_whole_rows():
    """What the fragment form's VJP keeps: no array whose shape follows a
    rung (the switch would keep every rung's, zero-filled, stacked over the
    envs), and no more bytes than the all-rows form keeps but the rungs'
    indices. Shapes with no size in common with a rung: 40 cached rows,
    rungs of 5, 10, 20 and 40, a fragment of 8."""
    L, T, tq = 40, 8, 4
    q, qi, w, mask, keys, values, ki = ladder_case([0, 6, 40], None, L, T)
    rungs = dsa._rungs(L, T)
    assert rungs == (5, 10, 20, 40)
    sliced = {n for c in rungs[:-1] for n in (c, c + T)}

    def kept(form):
        _, vjp = jax.vjp(lambda *a: form(*a[:3], mask, *a[3:])[0],
                         q, qi, w, keys, values, ki)
        return [np.asarray(x) for x in jax.tree.leaves(vjp)]

    mine = kept(lambda *a: dsa.dsa_fragment(*a, RK, SCALE, tq))
    ref = kept(lambda *a: all_rows(*a, tq=tq))
    assert not [x.shape for x in mine if sliced & set(x.shape)]
    ints = sum(x.nbytes for x in mine if x.dtype == np.int32)
    assert 0 < ints <= 4 * len(mine) * q.shape[0]
    assert sum(x.nbytes for x in mine) - ints <= sum(x.nbytes for x in ref)


def test_rows_computed_counted_by_hand():
    """At the benchmark's hand-counted shape (a cache of 8 rows, a fragment
    of 4 queries, one block): rungs of 1, 2, 4 and 8 rows, so envs holding
    0, 3 and 8 rows compute over 1, 4 and 8 cached rows and the fragment's
    4: 5 + 8 + 12 rows a query, 4 queries an env. A cache no longer than
    the fragment is one rung, its whole: 4 + 8 rows a query, 8 queries an
    env."""
    assert dsa._rungs(8, 4) == (1, 2, 4, 8)
    assert dsa._rungs(8192, 512) == (1024, 2048, 4096, 8192)
    _, counted = dsa.dsa_fragment(*ladder_case([0, 3, 8], None, 8, 4), 3, SCALE, 4)
    assert float(counted["dsa_rows_computed"]) == 4 * (5 + 8 + 12)
    assert dsa._rungs(4, 8) == (4,) and dsa._rungs(8, 8) == (8,)
    _, counted = dsa.dsa_fragment(*ladder_case([0, 3], None, 4, 8), 3, SCALE, 4)
    assert float(counted["dsa_rows_computed"]) == 2 * 8 * (4 + 8)
