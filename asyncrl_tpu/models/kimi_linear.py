"""A token-level recurrent policy of the Kimi-Linear family: KDA (gated
delta-rule linear attention, short conv 4) and NoPE MLA (latent attention)
mixers, a leading dense feed-forward and routed-expert layers of which this
chip holds a share, an output head over the held vocabulary slice and a
value head (the RL addition).

The policy's two forms (one token through the carry; a whole fragment from
the fragment-initial carry), its trunk, heads and counters, and the carry's
reset-on-read protocol are ``models/seq_common.py``'s, shared with the other
sequence policy (``models/lfm2_moe.py``); the MLA mixer is
``models/mla.py``'s, shared with ``models/moonlight.py`` (which rotates
what this model leaves unrotated). This module holds the shape record, the
KDA mixer and the weights. In the fragment form KDA runs chunkwise and MLA
under an episode mask.

The carry's entries: a KDA layer's ``{"S" [B, H, dk, dv] float32, "conv"
[B, 3, 3*H*dk], "fresh" [B] bool}``, an MLA layer's ``{"kv" [B, L, kv_lora +
rope], "len" [B] int32}``.

Precision: operands of the matrix products in ``compute_dtype``; KDA state,
decays, cumulative sums, softmax, router scores, norms and the head's
log-softmax in float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from asyncrl_tpu.models import mla
from asyncrl_tpu.models.seq_common import (  # noqa: F401  (SeqCore: the carry's type, by this name too)
    F32,
    SeqCore,
    SeqPolicyBase,
    TrunkScales,
    _dot,
    _rms_norm,
    _short_conv,
    _zero_where,
    seeded,
)
from asyncrl_tpu.ops import kda


@dataclasses.dataclass(frozen=True)
class SeqShape(TrunkScales):
    """Published widths and the cut: what ``Config.seq_model`` names."""

    hidden: int
    vocab: int  # the held slice
    layers: tuple[str, ...]  # "kda+dense" | "kda+moe" | "mla+moe"
    kda_heads: int
    kda_head_dim: int
    mla_heads: int
    qk_nope: int
    qk_rope: int
    v_head: int
    kv_lora: int
    dense_ffn: int
    expert_ffn: int
    num_experts: int  # the router's width
    held_experts: tuple[int, ...]  # ids of the experts this chip holds
    top_k: int
    routed_scale: float
    max_positions: int  # the latent cache's capacity = the episode cap
    conv_width: int = 4
    low_rank: int = 128  # of the decay and output-gate projections
    eps: float = 1e-5
    chunk: int = 64
    # The learner runs a layer over this many tokens at a time (whole envs:
    # nothing in a layer crosses envs), so a layer's activations are this
    # large and not the fragment's.
    block_tokens: int = 4096


SHAPES: dict[str, SeqShape] = {
    # Kimi-Linear-48B-A3B-Instruct's config.json at its published widths:
    # layers 1-5 of 27 (dense KDA, two expert KDA, the expert MLA, expert
    # KDA), experts 0-7 of 256, an eighth of the vocabulary: what one of
    # the 32 chips that share each layer holds.
    "kimi_linear_5l": SeqShape(
        hidden=2304, vocab=20480,
        layers=("kda+dense", "kda+moe", "kda+moe", "mla+moe", "kda+moe"),
        kda_heads=32, kda_head_dim=128,
        mla_heads=32, qk_nope=128, qk_rope=64, v_head=128, kv_lora=512,
        dense_ffn=9216, expert_ffn=1024, num_experts=256,
        held_experts=tuple(range(8)), top_k=8, routed_scale=2.446,
        max_positions=1024,
    ),
    # CPU tests: every kind of layer at toy widths.
    "kimi_linear_tiny": SeqShape(
        hidden=64, vocab=64, layers=("kda+dense", "kda+moe", "mla+moe"),
        kda_heads=2, kda_head_dim=16,
        mla_heads=2, qk_nope=16, qk_rope=8, v_head=16, kv_lora=24,
        dense_ffn=96, expert_ffn=32, num_experts=8,
        held_experts=(0, 1, 2, 3), top_k=2, routed_scale=2.446,
        max_positions=32, low_rank=8, block_tokens=128,
    ),
}


# ------------------------------------------------------------------ pieces


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda_mixer(p, x, state, done, shape: SeqShape, dtype):
    """``x`` [B, D] with ``done`` None (one token) or [T, B, D]."""
    H, dk = shape.kda_heads, shape.kda_head_dim
    with jax.named_scope("kda"):
        qkv = _dot(x, p["qkv"], dtype)
        qkv, conv = _short_conv(p["conv"], state["conv"], qkv, done)
        q, k, v = (
            t.reshape(*x.shape[:-1], H, dk)
            for t in jnp.split(jax.nn.silu(qkv), 3, axis=-1)
        )
        q, k = _l2_norm(q) * dk ** -0.5, _l2_norm(k)
        rate = _dot(_dot(x, p["f_down"], dtype), p["f_up"], dtype) + p["dt_bias"]
        g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            rate.reshape(*x.shape[:-1], H, dk)
        )
        beta = jax.nn.sigmoid(_dot(x, p["beta"], dtype))
        fresh = state["fresh"]
        if done is None:
            S, o = kda.kda_step(state["S"], q, k, v, g, beta, fresh)
        else:
            S, o = kda.kda_chunk(
                _zero_where(fresh, state["S"]), q, k, v, g, beta, done,
                chunk=shape.chunk, dtype=dtype,
            )
        gate = jax.nn.sigmoid(_dot(_dot(x, p["g_down"], dtype), p["g_up"], dtype))
        o = _rms_norm(o, p["o_norm"], shape.eps).reshape(*x.shape[:-1], H * dk)
        return _dot(o * gate, p["o"], dtype), {
            "S": S, "conv": conv, "fresh": jnp.zeros_like(fresh)}


# ------------------------------------------------------------------- model


@dataclasses.dataclass(frozen=True)
class SeqPolicy(SeqPolicyBase):
    """See the module docstring and ``seq_common.SeqPolicyBase``."""

    shape: SeqShape
    compute_dtype: Any = F32

    def initial_core(self, batch_size: int) -> SeqCore:
        s = self.shape
        n = 3 * s.kda_heads * s.kda_head_dim
        layers = []
        for kind in s.layers:
            if kind.startswith("kda"):
                layers.append({
                    "S": jnp.zeros(
                        (batch_size, s.kda_heads, s.kda_head_dim, s.kda_head_dim), F32
                    ),
                    "conv": jnp.zeros((batch_size, s.conv_width - 1, n), F32),
                    "fresh": jnp.zeros((batch_size,), bool),
                })
            else:
                layers.append({
                    "kv": jnp.zeros(
                        (batch_size, s.max_positions, s.kv_lora + s.qk_rope),
                        self.compute_dtype,
                    ),
                    "len": jnp.zeros((batch_size,), jnp.int32),
                })
        return SeqCore(tuple(layers))

    def init(self, key, obs=None, core=None):
        """Seeded random weights: projections N(0, 1/fan_in), unit-normal
        embedding, unit norms; KDA's ``A_log`` = log U(1, 16) and ``dt_bias``
        the inverse softplus of a log-uniform step in [1e-3, 1e-1] (the
        family's convention); the router's correction bias N(0, 0.02)."""
        s = self.shape
        w, keys = seeded(key, 64 * (len(s.layers) + 1))

        def swiglu(width, *lead):
            return {"gate": w(*lead, s.hidden, width), "up": w(*lead, s.hidden, width),
                    "down": w(*lead, width, s.hidden)}

        D, n_kda = s.hidden, s.kda_heads * s.kda_head_dim
        params = {"embed": jax.random.normal(next(keys), (s.vocab, D), F32)}
        for i, kind in enumerate(s.layers):
            mixer, ffn = kind.split("+")
            layer = {"norm_mixer": jnp.ones((D,), F32), "norm_ffn": jnp.ones((D,), F32)}
            if mixer == "kda":
                step = jnp.exp(jax.random.uniform(
                    next(keys), (n_kda,), F32, math.log(1e-3), math.log(1e-1)
                ))
                layer["kda"] = {
                    "qkv": w(D, 3 * n_kda),
                    "conv": w(s.conv_width, 3 * n_kda, fan_in=s.conv_width),
                    "f_down": w(D, s.low_rank), "f_up": w(s.low_rank, n_kda),
                    "A_log": jnp.log(jax.random.uniform(
                        next(keys), (s.kda_heads,), F32, 1.0, 16.0
                    )),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "beta": w(D, s.kda_heads),
                    "g_down": w(D, s.low_rank), "g_up": w(s.low_rank, n_kda),
                    "o_norm": jnp.ones((s.kda_head_dim,), F32),
                    "o": w(n_kda, D),
                }
            else:
                layer["mla"] = mla.weights(w, D, s)
            if ffn == "dense":
                layer["ffn"] = swiglu(s.dense_ffn)
            else:
                layer["ffn"] = {
                    "router": w(D, s.num_experts),
                    "router_bias": 0.02 * jax.random.normal(
                        next(keys), (s.num_experts,), F32
                    ),
                    "experts": swiglu(s.expert_ffn, len(s.held_experts)),
                    "shared": swiglu(s.expert_ffn),
                }
            params[f"layer_{i}"] = layer
        params["final_norm"] = jnp.ones((D,), F32)
        params["head"] = w(D, s.vocab)
        params["value"] = {"kernel": w(D, 1), "bias": jnp.zeros((1,), F32)}
        return {"params": params}

    def _mixer(self, p, mixer, x, state, done):
        s, dtype = self.shape, self.compute_dtype
        if mixer == "kda":
            y, state = _kda_mixer(p, x, state, done, s, dtype)
        elif done is None:  # NoPE: nothing of MLA is rotated
            y, state = mla.step(p, x, state, s, dtype)
        else:
            y, state = mla.fragment(p, x, state, done, s, dtype)
        return y, state, {}
