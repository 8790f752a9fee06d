"""Latent attention (MLA, DeepSeek-V2/V3): the layer of ``models/kimi_linear.py``
(NoPE: no key or query dim is rotated) and of ``models/moonlight.py``
(decoupled RoPE: the 64-dim rope half of each head's query and the one
64-dim rope key shared by the heads are rotated at the token's position in
its episode, ``theta`` the rotary base; ``theta=None`` rotates nothing).

A layer's carry is ``{"kv" [B, L, lora + rope], "len" [B] int32}``: a row
per position of the episode in progress, the normed latent ``c_kv`` and
beside it the shared rope key, rotated where the model rotates (so the
cache holds rotated rows and ``len`` is the next row's position). The
pairs of the rotation are ``seq_common._rotate``'s rotate-half pairs.

The one-token form (``step``) absorbs the up-projection ``kv_b`` into the
query and the output and attends over the latent rows (scope ``mla_step``)
by ``ops/latent.py latent_step``: on a TPU, at a latent of whole lane tiles
and a capacity of whole chunks (both published shapes), a Pallas kernel that
reads each env's rows up to ``len`` once, the whole row for the scores and
its latent lanes for the weighted rows; elsewhere the plain lines, both
products over the cache's whole capacity under a mask, as they were before
the kernel. The absorbed products and the row's write stay in XLA;
the fragment form (``fragment``) up-projects the cached and fragment rows
into per-head keys and values (scope ``mla_expand``) and runs a causal
softmax within the episode (scope ``mla_attend``), in blocks of envs, each
block over its rung of the cache (the cached rows its envs hold, rounded up
to an eighth, a quarter, a half or the whole capacity: ``ops/dsa.py
_rungs``, the ladder of the sparse mixer's fragment form) and the
fragment's rows.

``shape`` names ``mla_heads``, ``qk_nope``, ``qk_rope``, ``v_head``,
``kv_lora`` and ``eps``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from asyncrl_tpu.models.seq_common import (
    F32,
    _cache_after,
    _dot,
    _env_block,
    _episode_mask,
    _rms_norm,
    _rotate,
    _softmax,
    _to_blocks,
)
from asyncrl_tpu.ops.dsa import _rungs
from asyncrl_tpu.ops.latent import latent_step


def project(p, x, pos, shape, dtype, theta=None):
    """Queries [..., H, nope + rope] and the latent row [..., lora + rope]
    the cache holds (normed latent, then the shared rope key), the rope
    parts rotated at ``pos`` [...] where ``theta`` is given."""
    q = _dot(x, p["q"], dtype).reshape(
        *x.shape[:-1], shape.mla_heads, shape.qk_nope + shape.qk_rope
    )
    if theta is not None:
        q = jnp.concatenate(
            [q[..., : shape.qk_nope], _rotate(q[..., shape.qk_nope:], pos, theta)],
            axis=-1,
        )
    kv = _dot(x, p["kv_a"], dtype)
    c_kv = _rms_norm(kv[..., : shape.kv_lora], p["kv_norm"], shape.eps)
    k_pe = kv[..., shape.kv_lora:]
    if theta is not None:
        k_pe = _rotate(k_pe[..., None, :], pos, theta)[..., 0, :]
    return q, jnp.concatenate([c_kv, k_pe], axis=-1).astype(dtype)


def step(p, x, state, shape, dtype, theta=None):
    """One token: write its latent row at ``len``, attend over the rows of
    the current episode with the up-projection absorbed into the query and
    the output (no per-position keys or values are formed)."""
    H, dn, lora = shape.mla_heads, shape.qk_nope, shape.kv_lora
    with jax.named_scope("mla"):
        q, latent = project(p, x, state["len"], shape, dtype, theta)
        B = x.shape[0]
        cache = state["kv"].at[jnp.arange(B), state["len"]].set(latent)
        with jax.named_scope("mla_step"):
            kv_b = p["kv_b"].reshape(lora, H, dn + shape.v_head).astype(dtype)
            q_lat = jnp.einsum(
                "bhd,lhd->bhl", q[..., :dn].astype(dtype), kv_b[..., :dn],
                preferred_element_type=F32,
            )
            ctx = latent_step(
                jnp.concatenate([q_lat, q[..., dn:]], axis=-1).astype(dtype), cache,
                state["len"], lora, dn + shape.qk_rope,
            )
            out = jnp.einsum(
                "bhl,lhd->bhd", ctx.astype(dtype), kv_b[..., dn:],
                preferred_element_type=F32,
            )
        return (
            _dot(out.reshape(B, -1), p["o"], dtype),
            {"kv": cache, "len": state["len"] + 1},
        )


def fragment(p, x, state, done, shape, dtype, theta=None):
    """A fragment: keys and values materialised for the cached rows of the
    episode in progress and the fragment's own, causal softmax within the
    episode, in blocks of envs.

    Where the cache is longer than the fragment, a block is computed over
    the cache's first rows up to the smallest rung (``ops/dsa.py _rungs``:
    an eighth, a quarter, a half or the whole capacity) that holds every
    cached row its mask admits, and the fragment's rows, in their order:
    the rows left out are masked out for every query, so the result is the
    whole rows' but for the order of float sums. Each rung is a branch of
    ``lax.switch`` (``_branch``) over the whole rows, slicing inside."""
    H, dn = shape.mla_heads, shape.qk_nope
    T, B, _ = x.shape
    L = state["kv"].shape[1]
    with jax.named_scope("mla"):
        if theta is None:
            pos = None
        else:  # a token's position: the rows of its episode before it
            pos = jnp.sum(_episode_mask(done, state["len"], L)[0], axis=-1).T - 1
        q, latent = project(p, x, pos, shape, dtype, theta)
        rows = jnp.concatenate(
            [state["kv"], jnp.moveaxis(latent, 0, 1)], axis=1
        )  # [B, L + T, lora + rope]
        mask, ends = _episode_mask(done, state["len"], L)  # [B, T, L + T]

        n = _blocks(B, T, L, H)
        blocks = tuple(
            _to_blocks(a, 0, n) for a in (jnp.moveaxis(q, 0, 1), rows, mask)
        )
        rungs = _rungs(L, T)
        if len(rungs) == 1:
            block = lambda args: _attend(*args, p["kv_b"], dn, dtype)
        else:
            branches = [_branch(c, L, dn, dtype) for c in rungs]
            block = lambda args: jax.lax.switch(
                _rung_index(args[2], rungs), branches, *args, p["kv_b"])
        # rematerialised whole in the backward pass: what the map keeps of a
        # block is its operands, not the switch's residuals stacked over the
        # blocks (those took Moonlight's step, compiled for a v5e, 0.12 GB
        # more temporaries)
        out = jax.lax.map(jax.checkpoint(block), blocks)
        out = _dot(jnp.moveaxis(out.reshape(B, T, -1), 0, 1), p["o"], dtype)

        src, length = _cache_after(done, ends, state["len"], L)
        cache = jnp.take_along_axis(rows, src[..., None], axis=1)
        return out, {"kv": cache, "len": length}


def _blocks(B: int, T: int, L: int, heads: int) -> int:
    """How many blocks of envs the fragment form runs in."""
    return B // _env_block(B, heads * T * (L + T))


def _attend(q, rows, mask, kv_b, dn: int, dtype):
    """A block of envs' queries over their rows: ``q`` [b, T, H, dn + rope],
    ``rows`` [b, P, lora + rope], ``mask`` [b, T, P], ``kv_b`` [lora, H *
    (dn + v_head)] -> the heads' weighted values [b, T, H, v_head]."""
    H, lora = q.shape[2], kv_b.shape[0]
    with jax.named_scope("mla_expand"):
        kv = _dot(rows[..., :lora], kv_b, dtype).reshape(*rows.shape[:2], H, -1)
    with jax.named_scope("mla_attend"):
        scores = jnp.einsum(
            "bthd,bphd->bhtp", q[..., :dn].astype(dtype),
            kv[..., :dn].astype(dtype), preferred_element_type=F32,
        ) + jnp.einsum(
            "bthr,bpr->bhtp", q[..., dn:].astype(dtype), rows[..., lora:],
            preferred_element_type=F32,
        )
        probs = _softmax(scores / math.sqrt(q.shape[-1]), mask[:, None])
        return jnp.einsum(
            "bhtp,bphd->bthd", probs.astype(dtype),
            kv[..., dn:].astype(dtype), preferred_element_type=F32,
        )


def _rung_index(mask, rungs: tuple[int, ...]):
    """Which of ``rungs`` (the last the cache's capacity ``L``) a block's
    mask [..., b, T, L + T] needs: the smallest that holds every cached
    row the mask admits for any query -> [...] int32."""
    L = rungs[-1]
    held = jnp.max(jnp.where(
        jnp.any(mask[..., :L], axis=(-3, -2)), jnp.arange(1, L + 1), 0), axis=-1)
    return jnp.sum(held[..., None] > jnp.asarray(rungs[:-1]), axis=-1)


@functools.lru_cache(maxsize=None)
def _branch(c: int, L: int, dn: int, dtype):
    """A rung of the ladder: ``_attend`` over the cache's first ``c`` rows
    and the fragment's, a function of all ``L + T`` rows and of ``kv_b``.
    ``jax.checkpoint`` of ``jax.jit``, so that its residuals are its
    operands (one shape for every rung, which the switch's VJP merges into
    one set), and one function for every layer and call, which JAX traces,
    transforms and lowers once a program (``ops/dsa.py _rung``)."""

    def over(q, rows, mask, kv_b):
        if c < L:
            kept = lambda a, axis: jnp.concatenate(
                [jax.lax.slice_in_dim(a, 0, c, axis=axis),
                 jax.lax.slice_in_dim(a, L, a.shape[axis], axis=axis)], axis=axis)
            rows, mask = kept(rows, 1), kept(mask, 2)
        return _attend(q, rows, mask, kv_b, dn, dtype)

    return jax.checkpoint(jax.jit(over))


def counters(state, done, shape) -> dict:
    """What ``fragment`` did from the carry ``state`` over ``done`` [T, B],
    summed over the envs: ``mla_rows_attended`` the rows of its episode
    each query attended, ``mla_rows_expanded`` the latent rows the fragment
    form is handed (every env its cache's capacity and the fragment's
    rows), ``mla_rows_computed`` the latent rows an env's block was
    up-projected and scored over (its rung of the cache and the fragment's
    rows), ``mla_rows_cached`` the cached rows of the episodes in progress
    (the cached rows a fragment needs)."""
    T, B = done.shape
    L = state["kv"].shape[1]
    mask, _ = _episode_mask(done, state["len"], L)
    n = _blocks(B, T, L, shape.mla_heads)
    rungs = _rungs(L, T)
    rung = jnp.asarray(rungs)[
        _rung_index(mask.reshape(n, B // n, *mask.shape[1:]), rungs)]
    return {
        "mla_rows_attended": jnp.sum(mask).astype(F32),
        "mla_rows_expanded": jnp.asarray(B * (L + T), F32),
        "mla_rows_computed": (B // n * jnp.sum(rung + T)).astype(F32),
        "mla_rows_cached": jnp.sum(state["len"]).astype(F32),
    }


def weights(w, hidden: int, shape) -> dict:
    """A layer's seeded weights, ``w(*dims)`` the policy's seeded normal
    (``seq_common.seeded``), the latent's norm at unit scale."""
    H, lora = shape.mla_heads, shape.kv_lora
    return {
        "q": w(hidden, H * (shape.qk_nope + shape.qk_rope)),
        "kv_a": w(hidden, lora + shape.qk_rope),
        "kv_norm": jnp.ones((lora,), F32),
        "kv_b": w(lora, H * (shape.qk_nope + shape.v_head)),
        "o": w(H * shape.v_head, hidden),
    }
