"""A token-level policy of Keye-VL-2.0-30B-A3B's language model: grouped-query
attention (RoPE, per-head q/k RMS-norm) under a learned sparse-attention
indexer that picks the rows each query attends (``ops/dsa.py``: 16 index
heads of 64 over one key head, the top 2,048 rows), routed-expert layers
(a softmax over 128, the top 8 renormalised, no shared expert, every layer
sparse) of which this chip holds a share, an output head over the held
vocabulary slice and a value head (the RL addition). The vision tower and
the image positions are not here: text-only traffic has the three M-RoPE
position streams equal, which is the plain rotation.

The policy's two forms, its trunk, heads and counters, the carry's
reset-on-read protocol, the rotation and the grouped-query projection are
``models/seq_common.py``'s, shared with the other sequence policies. This
module holds the shape record, the mixer's two forms and the weights.

A layer's carry: ``{"k", "v" [B, L, Hkv * dh], "ki" [B, L, dI], "len" [B]
int32}``: the K/V cache of ``models/lfm2_moe.py`` (a position's key-value
heads side by side in one row, keys normed and rotated at their positions)
and beside it the indexer's key of each position, LayerNormed and rotated
(64 lanes: half a lane tile, so the array is padded to twice its bytes on
the chip; at 128 B a position against the 2 KB of keys and values that is
cheaper than a layout of its own). A token's position is its index in its
episode.

The indexer reads ``stop_gradient`` of the layer's normed input and the
selection passes no gradient, so the indexer's leaves are trained by its KL
term alone (``aux[MODEL_LOSS]``, which ``learn/learner.py`` adds to the
loss), and that term reaches nothing else.

Precision: operands of the matrix products in ``compute_dtype``; the q/k
norms, the indexer's LayerNorm, the rotations, ``relu`` and the index
scores' sum, the selection, both softmaxes, the KL term, router scores,
norms and the head's log-softmax in float32.
"""

from __future__ import annotations

import dataclasses
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
    _episode_mask,
    _gqa_project,
    _rotate,
    seeded,
)
from asyncrl_tpu.ops import dsa


@dataclasses.dataclass(frozen=True)
class KeyeShape(TrunkScales):
    """Published widths and the cut: what ``Config.seq_model`` names."""

    hidden: int
    vocab: int  # the held slice
    layers: tuple[str, ...]  # "dsa+moe"
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    index_heads: int
    index_dim: int
    index_top_k: int  # rows a query attends
    expert_ffn: int
    num_experts: int  # the router's width
    held_experts: tuple[int, ...]  # ids of the experts this chip holds
    top_k: int  # experts a token
    routed_scale: float
    max_positions: int  # the cache's capacity = the episode cap
    eps: float = 1e-6
    # The learner runs a layer over this many tokens at a time (whole envs),
    # and inside it attention over one env and this many queries at a time.
    block_tokens: int = 8192
    query_block: int = 128


SHAPES: dict[str, KeyeShape] = {
    # Keye-VL-2.0-30B-A3B's language model at its published widths: layers
    # 0-3 of 48 (every layer is of one kind), experts 0-15 of 128, an eighth
    # of the vocabulary: what one of the 8 chips that share each layer holds.
    "keye_moe_4l": KeyeShape(
        hidden=2048, vocab=18992, layers=("dsa+moe",) * 4,
        heads=32, kv_heads=4, head_dim=128, rope_theta=1e7,
        index_heads=16, index_dim=64, index_top_k=2048,
        expert_ffn=768, num_experts=128, held_experts=tuple(range(16)),
        top_k=8, routed_scale=1.0, max_positions=8192,
        # 8 envs a block: the step's scratch is 8.16 GB where 16 envs a
        # block take 8.77 (compiled for a described v5e), beside 6.73 of state
        block_tokens=4096,
    ),
    # CPU tests: the same layer at toy widths, a top-k smaller than the
    # episodes so that the selection prunes.
    "keye_moe_tiny": KeyeShape(
        hidden=64, vocab=64, layers=("dsa+moe",) * 2,
        heads=4, kv_heads=2, head_dim=16, rope_theta=1e7,
        index_heads=4, index_dim=8, index_top_k=8,
        expert_ffn=32, num_experts=8, held_experts=(0, 1, 2, 3),
        top_k=2, routed_scale=1.0, max_positions=32,
        block_tokens=128, query_block=16,
    ),
}


def _layer_norm(x, scale, bias, eps):
    x = jax.lax.optimization_barrier(x.astype(F32))  # as ``_rms_norm``
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _index_project(p, x, pos, shape: KeyeShape, dtype):
    """The indexer's queries [..., J, dI] (float32), its heads' weights
    [..., J] and the key row [..., dI] the cache holds, from the layer's
    normed input (no gradient passes back into it), rotated at ``pos``."""
    J, dI = shape.index_heads, shape.index_dim
    x = jax.lax.stop_gradient(x)
    qi = _dot(x, p["q"], dtype).reshape(*x.shape[:-1], J, dI)
    ki = _layer_norm(_dot(x, p["k"], dtype), p["k_norm"], p["k_bias"], shape.eps)
    qi = _rotate(qi, pos, shape.rope_theta)
    ki = _rotate(ki[..., None, :], pos, shape.rope_theta)[..., 0, :]
    return qi, _dot(x, p["w"], dtype), ki.astype(dtype)


def _index_scale(shape: KeyeShape) -> float:
    return shape.index_dim ** -0.5 * shape.index_heads ** -0.5


def _dsa_step(p, x, state, shape: KeyeShape, dtype):
    """One token: write its key, value and indexer-key rows at ``len``,
    score the rows of the current episode, attend the chosen ones."""
    with jax.named_scope("gqa"):
        B = x.shape[0]
        q, k, v = _gqa_project(p, x, state["len"], shape, dtype)
        with jax.named_scope("dsa_index"):
            qi, w, ki = _index_project(p["index"], x, state["len"], shape, dtype)
        at = (jnp.arange(B), state["len"])
        rows = {"k": state["k"].at[at].set(k), "v": state["v"].at[at].set(v),
                "ki": state["ki"].at[at].set(ki)}
        out = dsa.dsa_step(
            q, rows["k"], rows["v"], qi, w, rows["ki"], state["len"],
            shape.index_top_k, _index_scale(shape),
        )
        return (
            _dot(out.reshape(B, shape.heads * shape.head_dim), p["o"], dtype),
            {**rows, "len": state["len"] + 1},
        )


def _dsa_fragment(p, x, state, done, shape: KeyeShape, dtype, with_chosen=False):
    """A fragment: the cached rows of the episode in progress and the
    fragment's own; every query scores the rows of its episode up to itself
    and attends the chosen ones. Returns also the indexer's KL term and the
    selection's counters, summed over the queries."""
    T, B, _ = x.shape
    L = state["k"].shape[1]
    with jax.named_scope("gqa"):
        mask, ends = _episode_mask(done, state["len"], L)  # [B, T, L + T]
        pos = jnp.sum(mask, axis=-1).T - 1  # [T, B]: rows of its episode before it
        q, k, v = _gqa_project(p, x, pos, shape, dtype)
        with jax.named_scope("dsa_index"):
            qi, w, ki = _index_project(p["index"], x, pos, shape, dtype)
        rows = {
            name: jnp.concatenate([state[name], jnp.moveaxis(new, 0, 1)], axis=1)
            for name, new in (("k", k), ("v", v), ("ki", ki))
        }
        out, counted = dsa.dsa_fragment(
            *(jnp.moveaxis(a, 0, 1) for a in (q, qi, w)), mask,
            rows["k"], rows["v"], rows["ki"],
            shape.index_top_k, _index_scale(shape), shape.query_block,
            with_chosen,
        )
        out = _dot(jnp.moveaxis(out.reshape(B, T, -1), 0, 1), p["o"], dtype)
        src, length = _cache_after(done, ends, state["len"], L)
        take = lambda a: jnp.take_along_axis(a, src[..., None], axis=1)
        return (
            out, {**{name: take(a) for name, a in rows.items()}, "len": length},
            counted,
        )


# ------------------------------------------------------------------- model


@dataclasses.dataclass(frozen=True)
class KeyePolicy(SeqPolicyBase):
    """See the module docstring and ``seq_common.SeqPolicyBase``."""

    shape: KeyeShape
    compute_dtype: Any = F32
    record_selection: bool = False  # ``selected``'s: the mixer keeps its mask

    # the family's router: a softmax over all experts, the top k renormalised
    ROUTE_SCORE = "softmax"

    def initial_core(self, batch_size: int) -> SeqCore:
        s = self.shape
        rows = lambda width: jnp.zeros(
            (batch_size, s.max_positions, width), self.compute_dtype)
        return SeqCore(tuple(
            {"k": rows(s.kv_heads * s.head_dim), "v": rows(s.kv_heads * s.head_dim),
             "ki": rows(s.index_dim), "len": jnp.zeros((batch_size,), jnp.int32)}
            for _ in s.layers
        ))

    def init(self, key, obs=None, core=None):
        """Seeded random weights, as the other sequence policies':
        projections N(0, 1/fan_in), unit-normal embedding, unit norms, the
        indexer's LayerNorm at scale 1 and bias 0; no router bias."""
        s = self.shape
        w, keys = seeded(key, 16 * (len(s.layers) + 1))
        D, n_q, n_kv = s.hidden, s.heads * s.head_dim, s.kv_heads * s.head_dim
        held = len(s.held_experts)
        params = {"embed": jax.random.normal(next(keys), (s.vocab, D), F32)}
        for i, _ in enumerate(s.layers):
            params[f"layer_{i}"] = {
                "norm_mixer": jnp.ones((D,), F32), "norm_ffn": jnp.ones((D,), F32),
                "dsa": {
                    "q": w(D, n_q), "k": w(D, n_kv), "v": w(D, n_kv),
                    "q_norm": jnp.ones((s.head_dim,), F32),
                    "k_norm": jnp.ones((s.head_dim,), F32),
                    "o": w(n_q, D),
                    "index": {
                        "q": w(D, s.index_heads * s.index_dim),
                        "k": w(D, s.index_dim),
                        "k_norm": jnp.ones((s.index_dim,), F32),
                        "k_bias": jnp.zeros((s.index_dim,), F32),
                        "w": w(D, s.index_heads),
                    },
                },
                "ffn": {
                    "router": w(D, s.num_experts),
                    "experts": {
                        "gate": w(held, D, s.expert_ffn),
                        "up": w(held, D, s.expert_ffn),
                        "down": w(held, s.expert_ffn, D),
                    },
                },
            }
        params["final_norm"] = jnp.ones((D,), F32)
        params["head"] = w(D, s.vocab)
        params["value"] = {"kernel": w(D, 1), "bias": jnp.zeros((1,), F32)}
        return {"params": params}

    def _mixer(self, p, mixer, x, state, done):
        s, dtype = self.shape, self.compute_dtype
        if done is None:
            return (*_dsa_step(p, x, state, s, dtype), {})
        return _dsa_fragment(p, x, state, done, s, dtype, self.record_selection)

    def selected(self, params, tokens, done, core):
        """The rows each of a fragment's queries attends, by layer [B, T,
        L + T] (the cache's rows, then the fragment's): the fragment form's
        own selection, for whoever holds it against another (the benchmark's
        reference). Not on the learner's path."""
        recording = dataclasses.replace(self, record_selection=True)
        h = jnp.take(params["embed"], tokens, axis=0)
        chosen = []
        for i, kind in enumerate(self.shape.layers):
            h, _, seen = recording._layer(
                params[f"layer_{i}"], kind, h, core.layers[i], done)
            chosen.append(seen["chosen"])
        return chosen
