"""Attention that chooses its rows: a learned indexer scores every row of a
query's episode, the ``top_k`` rows of largest score are selected, and
grouped-query attention runs over the selected rows only (the
DeepSeek-Sparse-Attention mechanism). This module knows rows, ``len``, heads
and ``top_k``, and nothing of the model that calls it.

For a query ``t`` and a row ``s`` of its episode up to itself:

- the index score ``I[t, s] = scale * sum_j w[t, j] * relu(qi[t, j] .
  ki[s])`` over the indexer's ``J`` heads (``index_scores``; ``scale`` is
  the caller's, ``dI ** -0.5 * J ** -0.5`` in the published indexer);
- ``S_t``: the ``top_k`` rows of largest ``I[t, s]``, every row where the
  episode holds no more (``select``: exact, ties to the earlier row, as
  ``lax.top_k`` breaks them);
- the heads' softmax over ``S_t`` alone, query head ``j`` reading key-value
  head ``j // (H / G)`` of rows that hold a position's ``G`` key-value heads
  side by side (the cache's layout, ``ops/gqa.py``);
- the indexer's loss ``KL(P_t || softmax_{s in S_t} I[t, s])``, ``P_t`` the
  heads' probabilities summed over the heads and normalised over ``S_t``,
  under ``stop_gradient``: the selection passes no gradient, so this term is
  all that trains the indexer, and it reaches nothing else.

Two forms, one function:

- ``dsa_step``: one token over the cache. The indexer scores the cache's
  whole capacity (its key rows are 128 B a position) and ``select`` gives
  the chosen rows as a mask. Where it can (a TPU, rows of whole lane tiles:
  ``gqa._kernel_fits``), the heads attend by ``ops/gqa.py``'s kernel under
  that mask: the cache stays in HBM and an env's rows are copied up to
  ``len`` only, so the rows beyond never leave HBM, and of the rows copied
  those that were not chosen are masked out of the softmax. Elsewhere
  ``_attend_rows``: both products over the cache's WHOLE capacity under the
  mask, as ``ops/gqa.py``'s plain lines under ``len``. On the chip (PERF.md,
  PR 33 and PR 32; 16 envs, 8,192 rows of 512 lanes, bfloat16, top 2,048,
  the caches passed to each timed call as they lie; ms a call): the masked
  products over everything 0.382 (the cache streams at ~700 GB/s), the
  kernel 0.237 at lengths uniform in 100-8,191 and 0.130 at
  the lengths the traffic has (mean ~2,500 rows); ``lax.top_k`` + a gather
  of the selected rows + the products over 2,048 rows 1.84, 1.71 of them
  the gather (80 GB/s: a row at a time). (PR 32 read the kernel at 1.06:
  its timing loop handed the call a fresh copy of the cache every time.) A
  kernel that copied the chosen rows only would move a third of the bytes
  again, a row of 1 KB at a time; none is written here. ``process_record()
  ["dsa_sites"]`` counts ``"step_kernel"`` and ``"step"`` (the masked
  products), once per site and program lowered. The kernel's VJP is the
  masked products'. Where the cache's capacity is no more than ``top_k``
  every row is selected and ``gqa_step`` serves.
- ``dsa_fragment``: a fragment's queries over ``[cache, fragment]`` rows, in
  blocks of one env and ``query_block`` queries, each rematerialised in the
  backward pass: the scores of the env's rung of the cache (its cached rows
  rounded up to an eighth, a quarter, a half or the whole capacity) and of
  the fragment's rows are computed, and the softmax runs under the
  selection's mask (a gather of 2,048 rows a QUERY would move more than the
  products it saves). Returns the summed KL term and the selection's
  counters beside the heads' outputs.

``select`` finds the ``k``-th largest score by bisection on the scores' bits
(32 passes of compare-and-count, then the ties by their index): a mask, no
sort. On the chip it takes 0.036 ms for a decode step's [16, 8192] scores
where ``lax.top_k`` takes 0.089, and 0.12 ms for a block's [128, 8704] where
``lax.top_k`` takes 0.77 (PERF.md, PR 32).

Precision: the products' operands in the rows' dtype, float32 accumulation;
``relu``, the heads' weights, the scale, the selection, both softmaxes and
the KL term in float32; the probabilities cast to the rows' dtype for the
second product (the kernel casts them before they are normalised, the plain
lines after, as in ``ops/gqa.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from asyncrl_tpu.obs import introspect
from asyncrl_tpu.ops import gqa
from asyncrl_tpu.ops.site import site_primitive

F32 = jnp.float32


def index_scores(qi, w, ki, scale: float):
    """``qi`` [..., Q, J, dI] the indexer's queries, ``w`` [..., Q, J] their
    heads' weights (float32), ``ki`` [..., P, dI] the rows' indexer keys ->
    ``I`` [..., Q, P] float32."""
    products = jnp.einsum(
        "...qjd,...pd->...jqp", qi.astype(ki.dtype), ki,
        preferred_element_type=F32,
    )
    # a pass of its own, as ``models/seq_common.py _rms_norm``'s sum: fused
    # into the product's epilogue the sum over heads is tiled by what else
    # the program holds, and a last bit of a score moves a row in or out
    products = lax.optimization_barrier(jax.nn.relu(products))
    weights = jnp.moveaxis(w.astype(F32), -1, -2)[..., None]  # [..., J, Q, 1]
    return scale * jnp.sum(products * weights, axis=-3)


def _ordered(x):
    """float32 -> uint32 in the floats' order (-0 as +0)."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    bits = lax.bitcast_convert_type(x.astype(F32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select(scores, valid, k: int):
    """The ``k`` largest of ``scores`` [..., P] among ``valid`` [..., P]
    (all of them where there are no more than ``k``), ties to the lower
    index: a mask [..., P]. Exact: the ``k``-th largest is found bit by bit
    (the largest threshold that ``k`` rows still reach), then the ties at it
    are admitted in index order."""
    P = scores.shape[-1]
    u = jnp.where(valid, _ordered(scores), jnp.uint32(0))
    count = lambda hit: jnp.sum(hit, axis=-1, dtype=jnp.int32)
    kk = jnp.minimum(count(valid), k)
    zero = kk * 0  # varies over the mesh axes the scores vary over

    def value_bit(i, tau):
        cand = tau | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(u >= cand[..., None]) >= kk, cand, tau)

    tau = lax.fori_loop(0, 32, value_bit, zero.astype(jnp.uint32))
    above, ties = u > tau[..., None], valid & (u == tau[..., None])
    need = kk - count(above)
    idx = jnp.arange(P, dtype=jnp.int32)
    bits = P.bit_length()

    def index_bit(i, m):
        cand = m | (jnp.int32(1) << (bits - 1 - i))
        return jnp.where(count(ties & (idx < cand[..., None])) <= need, cand, m)

    m = lax.fori_loop(0, bits, index_bit, zero)
    return above | (ties & (idx < m[..., None]))


# ------------------------------------------------------------- one token


def dsa_step(q, keys, values, qi, w, ki, length, top_k: int, scale: float):
    """One token. ``q`` [B, H, dh] float32, normed and rotated; ``keys``,
    ``values`` [B, L, G * dh] and ``ki`` [B, L, dI], this token's rows
    written; ``qi`` [B, J, dI], ``w`` [B, J]; ``length`` [B] int32, the index
    of this token's row. Returns the heads' weighted values [B, H, dh]
    float32."""
    L = keys.shape[1]
    if L <= top_k:  # every row of any episode is selected
        return gqa.gqa_step(q, keys, values, length)
    with jax.named_scope("dsa_index"):
        scores = index_scores(qi[:, None], w[:, None], ki, scale)[:, 0]  # [B, L]
    with jax.named_scope("dsa_select"):
        chosen = select(scores, jnp.arange(L)[None, :] <= length[:, None], top_k)
    with jax.named_scope("dsa_attend"):
        if not gqa._kernel_fits(q.shape, keys.shape, keys.dtype, masked=True):
            introspect.count_dsa_site("step")
            return _attend_rows(q, keys, values, chosen)
        return lax.platform_dependent(
            q, keys, values, length, chosen,
            tpu=lambda q, *xs: _kernel_attend(
                _site_p.bind(q, path="step_kernel"), *xs),
            default=lambda q, keys, values, _, chosen: _attend_rows(
                _site_p.bind(q, path="step"), keys, values, chosen),
        )


# Which form a site whose shape fits ended on is known where it is lowered.
_site_p = site_primitive("dsa_site", introspect.count_dsa_site)


@jax.custom_vjp
def _kernel_attend(q, keys, values, length, chosen):
    """``ops/gqa.py``'s kernel under the mask of chosen rows, with the plain
    lines' VJP (the learner differentiates the fragment form; only its
    bootstrap token comes by here)."""
    return gqa._kernel_step(q, keys, values, length, chosen)


def _kernel_attend_fwd(q, keys, values, length, chosen):
    out = gqa._kernel_step(q, keys, values, length, chosen)
    return out, (q, keys, values, chosen)


def _kernel_attend_bwd(xs, cotangent):
    *operands, chosen = xs
    _, vjp = jax.vjp(lambda *o: _attend_rows(*o, chosen), *operands)
    return (*vjp(cotangent), None, None)


_kernel_attend.defvjp(_kernel_attend_fwd, _kernel_attend_bwd)


def _attend_rows(q, keys, values, mask):
    """``ops/gqa.py _plain_step`` under a mask of rows [B, L] instead of a
    length: both products over the whole rows of the whole cache, batched
    over envs only, a query laid into its key-value head's lanes."""
    B, H, dh = q.shape
    G, dtype = keys.shape[-1] // dh, keys.dtype
    scores = jnp.einsum(
        "bhc,bpc->bhp", gqa._laid(q, G, dtype), keys, preferred_element_type=F32,
    ) / math.sqrt(dh)
    probs = _masked_softmax(scores, mask[:, None, :])
    out = jnp.einsum(
        "bhp,bpc->bhc", probs.astype(dtype), values, preferred_element_type=F32,
    )
    return jnp.sum(
        out.reshape(B, H, G, dh) * gqa._own(H, G)[None, :, :, None], axis=2)


# -------------------------------------------------------------- fragment


def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    e = jnp.where(mask, jnp.exp(scores), 0.0)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _block(q, qi, w, mask, keys, values, ki, top_k, scale, groups,
           with_chosen=False):
    """One env's ``tq`` queries over its ``P`` rows: ``q`` [tq, H, dh],
    ``qi`` [tq, J, dI], ``w`` [tq, J], ``mask`` [tq, P]; ``keys``, ``values``
    [P, G * dh], ``ki`` [P, dI] -> (out [tq, H, dh], [kl, rows scored, rows
    selected, queries pruned] summed over the queries, and with
    ``with_chosen`` the selection [tq, P])."""
    tq, H, dh = q.shape
    G, dtype = groups, keys.dtype
    with jax.named_scope("dsa_index"):
        scores = index_scores(qi, w, ki, scale)  # [tq, P]
    with jax.named_scope("dsa_select"):
        chosen = select(lax.stop_gradient(scores), mask, top_k)
    with jax.named_scope("dsa_attend"):
        keys, values = (a.reshape(-1, G, dh) for a in (keys, values))
        attn = jnp.einsum(
            "tgjd,pgd->gjtp", q.reshape(tq, G, H // G, dh).astype(dtype), keys,
            preferred_element_type=F32,
        ) / math.sqrt(dh)
        probs = _masked_softmax(attn, chosen[None, None])
        out = jnp.einsum(
            "gjtp,pgd->tgjd", probs.astype(dtype), values,
            preferred_element_type=F32,
        ).reshape(tq, H, dh)
    with jax.named_scope("dsa_index"):
        # KL(P || softmax over the chosen rows of I): the heads' softmaxes
        # each sum to 1 over the chosen rows, so their mean is P
        target = lax.stop_gradient(jnp.mean(probs, axis=(0, 1)))  # [tq, P]
        index = jnp.where(chosen, scores, -jnp.inf)
        top = lax.stop_gradient(jnp.max(index, axis=-1, keepdims=True))
        log_pi = scores - top - jnp.log(jnp.sum(
            jnp.where(chosen, jnp.exp(index - top), 0.0), axis=-1, keepdims=True))
        live = chosen & (target > 0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_pi), 0.0))
    scored = jnp.sum(mask, axis=-1)
    counted = jnp.stack([
        kl, jnp.sum(scored).astype(F32), jnp.sum(chosen).astype(F32),
        jnp.sum(scored > top_k).astype(F32),
    ])
    return (out, counted, chosen) if with_chosen else (out, counted)


def _rungs(L: int, T: int) -> tuple[int, ...]:
    """The cached rows an env's blocks may be computed over: an eighth, a
    quarter, a half or all of the cache's ``L`` (rounded up); ``L`` alone
    where the cache is no longer than the fragment (``models/mla.py
    fragment`` climbs the same ladder). Few rungs, as each is
    compiled code in every layer and pass (eight, in eighths, made Keye's
    step twice the executable and its set-up ~15 s longer), and none
    between a half and the whole (there, eighths' rungs of 6,144 and 7,168
    rows took ~200 ms an env and update more than their rows in the cell:
    PERF.md §6, PR 37)."""
    if L <= T:
        return (L,)
    return tuple(sorted({-(-L // 2 ** k) for k in range(4)}))


def dsa_fragment(q, qi, w, mask, keys, values, ki, top_k: int, scale: float,
                 query_block: int, with_chosen: bool = False):
    """A fragment. ``q`` [B, T, H, dh] float32, normed and rotated; ``qi``
    [B, T, J, dI], ``w`` [B, T, J]; ``mask`` [B, T, L + T]: the rows of a
    query's own episode up to itself; ``keys``, ``values`` [B, L + T, G *
    dh], ``ki`` [B, L + T, dI]: the ``L`` cached rows and the fragment's
    own. Returns (the heads' weighted values [B, T, H, dh] float32,
    {"indexer_kl", "dsa_rows_scored", "dsa_rows_selected",
    "dsa_pruned_share", "dsa_rows_computed"}: sums over the B * T queries;
    with ``with_chosen`` also "chosen" [B, T, L + T], the rows each query
    attended).

    An env's blocks are computed over the cache's first rows up to the
    smallest rung (``_rungs``) that holds every cached row its mask admits,
    and the fragment's rows, in their order: the rows left out are masked
    out for every query, so the result is the whole rows' but for the
    order of float sums. Each rung is a branch of ``lax.switch`` under
    ``jax.checkpoint`` over the whole rows, slicing inside: the branches
    save residuals of one shape, which the switch's VJP merges into one
    set (sliced residuals would be kept for every rung, zero-filled)."""
    B, T, H, dh = q.shape
    L = keys.shape[1] - T
    G = keys.shape[-1] // dh
    tq = math.gcd(T, query_block)
    rungs = _rungs(L, T)
    static = (L, T, tq, top_k, scale, G, with_chosen)
    if len(rungs) == 1:
        env = lambda args: _env_over(L, *static)(*args)
    else:
        branches = [_rung(c, *static) for c in rungs]

        def env(args):
            held = jnp.max(jnp.where(
                jnp.any(args[3][:, :L], axis=0), jnp.arange(1, L + 1), 0))
            rung = jnp.sum(held > jnp.asarray(rungs[:-1]))
            return lax.switch(rung, branches, *args)

    out, counted, *chosen = lax.map(env, (q, qi, w, mask, keys, values, ki))
    names = ("indexer_kl", "dsa_rows_scored", "dsa_rows_selected",
             "dsa_pruned_share", "dsa_rows_computed")
    counted = dict(zip(names, jnp.sum(counted, axis=0)))
    if with_chosen:
        counted["chosen"] = chosen[0]
    return out, counted


def _env_over(c: int, L: int, T: int, tq: int, top_k: int, scale: float,
              groups: int, with_chosen: bool):
    """One env's blocks over the cache's first ``c`` rows and the
    fragment's ``T``, a function of all ``L + T`` rows."""

    def env(q, qi, w, mask, keys, values, ki):
        if c < L:
            kept = lambda a, axis=0: jnp.concatenate(
                [lax.slice_in_dim(a, 0, c, axis=axis),
                 lax.slice_in_dim(a, L, L + T, axis=axis)], axis=axis)
            keys, values, ki, mask = kept(keys), kept(values), kept(ki), kept(mask, 1)
        blocks = lambda a: a.reshape(T // tq, tq, *a.shape[1:])
        out, counted, *chosen = lax.map(
            jax.checkpoint(lambda xs: _block(
                *xs, keys, values, ki, top_k, scale, groups, with_chosen)),
            tuple(blocks(a) for a in (q, qi, w, mask)),
        )
        counted = jnp.append(jnp.sum(counted, axis=0), F32(T * (c + T)))
        return (out.reshape(T, *out.shape[2:]), counted,
                *(_widen(ch.reshape(T, -1), c, L) for ch in chosen))

    return env


@functools.lru_cache(maxsize=None)
def _rung(*static):
    """A branch of the ladder, ``_env_over``'s: ``jax.checkpoint`` of
    ``jax.jit``, so that its residuals are its operands, and one function
    for every layer and call, which JAX traces, transforms and lowers once
    a program (traced apiece in each of four layers, eight rungs made
    Keye's step take twice as long to lower)."""
    return jax.checkpoint(jax.jit(_env_over(*static)))


def _widen(chosen, c: int, L: int):
    """A selection over the cache's first ``c`` rows and the fragment's
    [T, c + T] -> over all ``L + T`` rows."""
    if c == L:
        return chosen
    T = chosen.shape[0]
    return jnp.concatenate(
        [chosen[:, :c], jnp.zeros((T, L - c), bool), chosen[:, c:]], axis=1)
