"""A token-level policy of the LFM2-MoE family (LFM2-8B-A1B): gated short
convolution and grouped-query attention (RoPE, per-head q/k RMS-norm)
mixers, a leading dense feed-forward and routed-expert layers (sigmoid
scores, 4 of 32, no shared expert) of which this chip holds a share, an
output head over the held vocabulary slice and a value head (the RL
addition).

The policy's two forms (one token through the carry; a whole fragment from
the fragment-initial carry), its trunk, heads and counters, and the carry's
reset-on-read protocol are ``models/seq_common.py``'s, shared with the other
sequence policy (``models/kimi_linear.py``). This module holds the shape
record, the two mixers and the weights.

The carry's entries: a conv layer's ``{"conv" [B, 2, D] float32}``, the
gated inputs ``u`` of the episode's last two tokens; an attention layer's
``{"k", "v" [B, L, Hkv * dh], "len" [B] int32}``, a position's key-value
heads side by side in one row (512 lanes at the published widths: a row of
``[Hkv, dh]`` tiles would leave half of every lane tile empty, and XLA
then re-lays the whole cache for each of a decode step's two products),
the keys normed and rotated at their positions. A token's position is its index in its episode: the
cache's ``len`` before its row is written, so positions continue across a
fragment's boundary and restart where an episode ends inside a fragment.

Precision: operands of the matrix products in ``compute_dtype``; the conv
and its gates, the q/k norms, the rotation, softmax, router scores, norms
and the head's log-softmax in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from asyncrl_tpu.models.seq_common import (
    F32,
    SeqCore,
    SeqPolicyBase,
    TrunkScales,
    _cache_after,
    _dot,
    _env_block,
    _episode_mask,
    _gqa_project,
    _rms_norm,
    _short_conv,
    _softmax,
    _to_blocks,
    seeded,
)
from asyncrl_tpu.ops.gqa import gqa_step


@dataclasses.dataclass(frozen=True)
class Lfm2Shape(TrunkScales):
    """Published widths and the cut: what ``Config.seq_model`` names."""

    hidden: int
    vocab: int  # the held slice
    layers: tuple[str, ...]  # "conv+dense" | "conv+moe" | "gqa+moe"
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    dense_ffn: int
    expert_ffn: int
    num_experts: int  # the router's width
    held_experts: tuple[int, ...]  # ids of the experts this chip holds
    top_k: int
    routed_scale: float
    max_positions: int  # the K/V cache's capacity = the episode cap
    conv_width: int = 3
    eps: float = 1e-5
    # The learner runs a layer over this many tokens at a time (whole envs:
    # nothing in a layer crosses envs), so a layer's activations are this
    # large and not the fragment's.
    block_tokens: int = 8192


SHAPES: dict[str, Lfm2Shape] = {
    # LFM2-8B-A1B's config.json at its published widths: layers 1-5 of 24
    # (conv + dense, attention + experts, three conv + experts), experts
    # 0-7 of 32, a quarter of the vocabulary: what one of the 4 chips that
    # share each layer holds.
    "lfm2_moe_5l": Lfm2Shape(
        hidden=2048, vocab=16384,
        layers=("conv+dense", "gqa+moe", "conv+moe", "conv+moe", "conv+moe"),
        heads=32, kv_heads=8, head_dim=64, rope_theta=1e6,
        dense_ffn=7168, expert_ffn=1792, num_experts=32,
        held_experts=tuple(range(8)), top_k=4, routed_scale=1.0,
        max_positions=2048,
    ),
    # CPU tests: every kind of layer at toy widths.
    "lfm2_moe_tiny": Lfm2Shape(
        hidden=64, vocab=64, layers=("conv+dense", "gqa+moe", "conv+moe"),
        heads=4, kv_heads=2, head_dim=16, rope_theta=1e6,
        dense_ffn=96, expert_ffn=32, num_experts=8,
        held_experts=(0, 1, 2, 3), top_k=2, routed_scale=1.0,
        max_positions=32, block_tokens=128,
    ),
}


# ------------------------------------------------------------------ mixers


def _conv_mixer(p, x, state, done, dtype):
    """``C * conv3(B * x~)``, no activation: ``x`` [B, D] with ``done`` None
    (one token) or [T, B, D]."""
    with jax.named_scope("conv_mixer"):
        b, c, xt = jnp.split(_dot(x, p["in"], dtype), 3, axis=-1)
        v, tail = _short_conv(p["conv"], state["conv"], b * xt, done)
        return _dot(c * v, p["out"], dtype), {"conv": tail}


def _gqa_step(p, x, state, shape: Lfm2Shape, dtype):
    """One token: write its key and value rows at ``len``, attend over the
    rows of the current episode (``ops/gqa.py``: the cache is read up to
    ``len`` where its kernel runs, whole under a mask elsewhere); query head
    j reads key-value head j // 4. The cache's arrays go to it as the write
    leaves them: rows of 512, env-major, nothing copied."""
    with jax.named_scope("gqa"):
        B = x.shape[0]
        q, k, v = _gqa_project(p, x, state["len"], shape, dtype)
        at = (jnp.arange(B), state["len"])
        keys, values = state["k"].at[at].set(k), state["v"].at[at].set(v)
        out = gqa_step(q, keys, values, state["len"])
        return (
            _dot(out.reshape(B, shape.heads * shape.head_dim), p["o"], dtype),
            {"k": keys, "v": values, "len": state["len"] + 1},
        )


def _gqa_fragment(p, x, state, done, shape: Lfm2Shape, dtype):
    """A fragment: the cached rows of the episode in progress and the
    fragment's own, causal softmax within the episode, in blocks of envs so
    that the [B, H, T, L + T] scores are never whole. Returns also the rows
    its queries attended, summed."""
    H, G, dh = shape.heads, shape.kv_heads, shape.head_dim
    T, B, _ = x.shape
    L = state["k"].shape[1]
    with jax.named_scope("gqa"):
        mask, ends = _episode_mask(done, state["len"], L)  # [B, T, L + T]
        # a token's position: the rows of its episode before it, cached or
        # the fragment's own (its mask's row holds them and itself)
        pos = jnp.sum(mask, axis=-1).T - 1  # [T, B]
        q, k, v = _gqa_project(p, x, pos, shape, dtype)
        keys = jnp.concatenate([state["k"], jnp.moveaxis(k, 0, 1)], axis=1)
        values = jnp.concatenate([state["v"], jnp.moveaxis(v, 0, 1)], axis=1)

        def attend(args):
            q, keys, values, mask = args  # [b, T, H, dh], [b, L+T, G * dh] x2, [b, T, L+T]
            keys, values = (a.reshape(*a.shape[:2], G, dh) for a in (keys, values))
            scores = jnp.einsum(
                "btgjd,bpgd->bgjtp",
                q.reshape(*q.shape[:2], G, H // G, dh).astype(dtype), keys,
                preferred_element_type=F32,
            ) / math.sqrt(dh)
            probs = _softmax(scores, mask[:, None, None])
            return jnp.einsum(
                "bgjtp,bpgd->btgjd", probs.astype(dtype), values,
                preferred_element_type=F32,
            )

        n = B // _env_block(B, H * T * (L + T))
        out = jax.lax.map(
            jax.checkpoint(attend),
            tuple(_to_blocks(a, 0, n)
                  for a in (jnp.moveaxis(q, 0, 1), keys, values, mask)),
        ).reshape(B, T, H * dh)
        out = _dot(jnp.moveaxis(out, 0, 1), p["o"], dtype)
        src, length = _cache_after(done, ends, state["len"], L)
        take = lambda rows: jnp.take_along_axis(rows, src[..., None], axis=1)
        return (
            out, {"k": take(keys), "v": take(values), "len": length},
            {"rows_attended": jnp.sum(pos + 1).astype(F32)},
        )


# ------------------------------------------------------------------- model


@dataclasses.dataclass(frozen=True)
class Lfm2Policy(SeqPolicyBase):
    """See the module docstring and ``seq_common.SeqPolicyBase``."""

    shape: Lfm2Shape
    compute_dtype: Any = F32

    # the family's router renormalises by the chosen scores' sum + 1e-6
    ROUTE_EPS = 1e-6

    def initial_core(self, batch_size: int) -> SeqCore:
        s = self.shape
        rows = (batch_size, s.max_positions, s.kv_heads * s.head_dim)
        return SeqCore(tuple(
            {"conv": jnp.zeros((batch_size, s.conv_width - 1, s.hidden), F32)}
            if kind.startswith("conv") else
            {"k": jnp.zeros(rows, self.compute_dtype),
             "v": jnp.zeros(rows, self.compute_dtype),
             "len": jnp.zeros((batch_size,), jnp.int32)}
            for kind in s.layers
        ))

    def init(self, key, obs=None, core=None):
        """Seeded random weights, as the other sequence policy's:
        projections N(0, 1/fan_in), unit-normal embedding, unit norms; the
        router's expert bias N(0, 0.02), a buffer."""
        s = self.shape
        w, keys = seeded(key, 16 * (len(s.layers) + 1))

        def swiglu(width, *lead):
            return {"gate": w(*lead, s.hidden, width), "up": w(*lead, s.hidden, width),
                    "down": w(*lead, width, s.hidden)}

        D, n_q, n_kv = s.hidden, s.heads * s.head_dim, s.kv_heads * s.head_dim
        params = {"embed": jax.random.normal(next(keys), (s.vocab, D), F32)}
        for i, kind in enumerate(s.layers):
            mixer, ffn = kind.split("+")
            layer = {"norm_mixer": jnp.ones((D,), F32), "norm_ffn": jnp.ones((D,), F32)}
            if mixer == "conv":
                layer["conv"] = {
                    "in": w(D, 3 * D),  # B, C, x~ side by side, in that order
                    "conv": w(s.conv_width, D, fan_in=s.conv_width),
                    "out": w(D, D),
                }
            else:
                layer["gqa"] = {
                    "q": w(D, n_q), "k": w(D, n_kv), "v": w(D, n_kv),
                    "q_norm": jnp.ones((s.head_dim,), F32),
                    "k_norm": jnp.ones((s.head_dim,), F32),
                    "o": w(n_q, D),
                }
            if ffn == "dense":
                layer["ffn"] = swiglu(s.dense_ffn)
            else:
                layer["ffn"] = {
                    "router": w(D, s.num_experts),
                    "router_bias": 0.02 * jax.random.normal(
                        next(keys), (s.num_experts,), F32
                    ),
                    "experts": swiglu(s.expert_ffn, len(s.held_experts)),
                }
            params[f"layer_{i}"] = layer
        params["final_norm"] = jnp.ones((D,), F32)
        params["head"] = w(D, s.vocab)
        params["value"] = {"kernel": w(D, 1), "bias": jnp.zeros((1,), F32)}
        return {"params": params}

    def _mixer(self, p, mixer, x, state, done):
        s, dtype = self.shape, self.compute_dtype
        if mixer == "conv":
            return (*_conv_mixer(p, x, state, done, dtype), {})
        if done is None:
            return (*_gqa_step(p, x, state, s, dtype), {})
        return _gqa_fragment(p, x, state, done, s, dtype)
