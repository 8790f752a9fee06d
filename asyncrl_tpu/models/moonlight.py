"""A token-level policy of Moonlight-16B-A3B (``deepseek_v3``): latent
attention (MLA) with decoupled RoPE in every layer (``models/mla.py``: 16
heads, a 512-dim latent and a 64-dim rope key shared by the heads, both
cached, the rope halves of queries and key rotated at the token's position
in its episode, base 50,000), a leading dense feed-forward, then
routed-expert layers (a sigmoid over 64 with a correction bias, the top 6
renormalised and scaled by 2.446, two shared experts that are one SwiGLU of
twice the expert width) of which this chip holds a share, an output head
over the held vocabulary slice and a value head (the RL addition).

The policy's two forms, its trunk, heads and counters and the carry's
reset-on-read protocol are ``models/seq_common.py``'s; the mixer is
``models/mla.py``'s, the one ``models/kimi_linear.py`` runs unrotated. This
module holds the shape record and the weights.

A layer's carry: ``{"kv" [B, L, kv_lora + qk_rope], "len" [B] int32}``, the
normed latent and the rotated rope key of each position of the episode in
progress.

The rotation pairs dims as ``seq_common._rotate`` does (rotate-half: dim i
with dim i + 32); the published code pairs adjacent dims and permutes them
to the half layout before rotating. The two are one function under a fixed
permutation of the 64 rope columns of ``q`` (each head's) and of ``kv_a``
(``benchmarks/reference/moonlight.py`` rotates as published and is handed
those columns permuted).

Precision: operands of the matrix products in ``compute_dtype``; the
rotation, softmax, router scores, norms and the head's log-softmax in
float32; the cache's rows in ``compute_dtype``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from asyncrl_tpu.models import mla
from asyncrl_tpu.models.seq_common import F32, SeqCore, SeqPolicyBase, TrunkScales, seeded


@dataclasses.dataclass(frozen=True)
class MoonlightShape(TrunkScales):
    """Published widths and the cut: what ``Config.seq_model`` names."""

    hidden: int
    vocab: int  # the held slice
    layers: tuple[str, ...]  # "mla+dense" | "mla+moe"
    mla_heads: int
    qk_nope: int
    qk_rope: int
    v_head: int
    kv_lora: int
    rope_theta: float
    dense_ffn: int
    expert_ffn: int
    shared_ffn: int  # the shared experts, side by side in one SwiGLU
    num_experts: int  # the router's width
    held_experts: tuple[int, ...]  # ids of the experts this chip holds
    top_k: int
    routed_scale: float
    max_positions: int  # the latent cache's capacity = the episode cap
    eps: float = 1e-5
    # The learner runs a layer over this many tokens at a time (whole envs).
    block_tokens: int = 4096


SHAPES: dict[str, MoonlightShape] = {
    # Moonlight-16B-A3B's config.json at its published widths: layers 0-4 of
    # 27 (the dense one and four expert layers), experts 0-7 of 64, an
    # eighth of the vocabulary: what one of the 8 chips that share each
    # layer holds.
    "moonlight_5l": MoonlightShape(
        hidden=2048, vocab=20480, layers=("mla+dense",) + ("mla+moe",) * 4,
        mla_heads=16, qk_nope=128, qk_rope=64, v_head=128, kv_lora=512,
        rope_theta=50000.0, dense_ffn=11264, expert_ffn=1408, shared_ffn=2816,
        num_experts=64, held_experts=tuple(range(8)), top_k=6,
        routed_scale=2.446, max_positions=8192,
        # 4 envs a block: the step's scratch is 7.44 GB where 8 envs a block
        # take 8.14 (compiled for a described v5e), beside 7.58 of state
        block_tokens=2048,
    ),
    # CPU tests: both kinds of layer at toy widths.
    "moonlight_tiny": MoonlightShape(
        hidden=64, vocab=64, layers=("mla+dense", "mla+moe", "mla+moe"),
        mla_heads=2, qk_nope=16, qk_rope=8, v_head=16, kv_lora=24,
        rope_theta=50000.0, dense_ffn=96, expert_ffn=32, shared_ffn=64,
        num_experts=8, held_experts=(0, 1, 2, 3), top_k=2, routed_scale=2.446,
        max_positions=32, block_tokens=128,
    ),
}


@dataclasses.dataclass(frozen=True)
class MoonlightPolicy(SeqPolicyBase):
    """See the module docstring and ``seq_common.SeqPolicyBase``."""

    shape: MoonlightShape
    compute_dtype: Any = F32

    def initial_core(self, batch_size: int) -> SeqCore:
        s = self.shape
        return SeqCore(tuple(
            {"kv": jnp.zeros((batch_size, s.max_positions, s.kv_lora + s.qk_rope),
                             self.compute_dtype),
             "len": jnp.zeros((batch_size,), jnp.int32)}
            for _ in s.layers
        ))

    def init(self, key, obs=None, core=None):
        """Seeded random weights, as the other sequence policies':
        projections N(0, 1/fan_in), unit-normal embedding, unit norms; the
        router's correction bias N(0, 0.02), a buffer."""
        s = self.shape
        w, keys = seeded(key, 16 * (len(s.layers) + 1))
        D = s.hidden

        def swiglu(width, *lead):
            return {"gate": w(*lead, D, width), "up": w(*lead, D, width),
                    "down": w(*lead, width, D)}

        params = {"embed": jax.random.normal(next(keys), (s.vocab, D), F32)}
        for i, kind in enumerate(s.layers):
            layer = {"norm_mixer": jnp.ones((D,), F32), "norm_ffn": jnp.ones((D,), F32),
                     "mla": mla.weights(w, D, s)}
            if kind.endswith("+dense"):
                layer["ffn"] = swiglu(s.dense_ffn)
            else:
                layer["ffn"] = {
                    "router": w(D, s.num_experts),
                    "router_bias": 0.02 * jax.random.normal(
                        next(keys), (s.num_experts,), F32),
                    "experts": swiglu(s.expert_ffn, len(s.held_experts)),
                    "shared": swiglu(s.shared_ffn),
                }
            params[f"layer_{i}"] = layer
        params["final_norm"] = jnp.ones((D,), F32)
        params["head"] = w(D, s.vocab)
        params["value"] = {"kernel": w(D, 1), "bias": jnp.zeros((1,), F32)}
        return {"params": params}

    def _mixer(self, p, mixer, x, state, done):
        s, dtype = self.shape, self.compute_dtype
        if done is None:
            return (*mla.step(p, x, state, s, dtype, s.rope_theta), {})
        y, after = mla.fragment(p, x, state, done, s, dtype, s.rope_theta)
        return y, after, jax.lax.stop_gradient(mla.counters(state, done, s))
