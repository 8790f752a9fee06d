"""A token-level policy of Granite 4.0-H (``granitemoehybrid`` with no
experts): Mamba-2 state-space mixers (``ops/ssd.py``: 64 heads of 64, a state
of 128, ``B`` and ``C`` shared by the heads, a causal conv of 4 over ``[x,
B, C]`` and a gated norm after the read-out) with one grouped-query attention
layer in ten (32 query heads and 8 key-value heads of 64, no positions: NoPE,
no q/k norm, the softmax scaled by ``attention_multiplier``), a dense SwiGLU
in every layer, muP multipliers on the embedding, the residual branches and
the logits, an output head tied to the embedding, and a value head (the RL
addition).

The policy's two forms, its trunk (the three multipliers and the tied head
among it), heads and counters and the carry's reset-on-read protocol are
``models/seq_common.py``'s; the attention mixer is ``models/lfm2_moe.py``'s
with nothing rotated or normed (``ops/gqa.py``'s one-token kernel: the
multiplier 1/64 is folded into the queries as 1/8, exact in float, since the
kernel divides by sqrt(64)). This module holds the shape record, the Mamba
mixer and the weights.

A Mamba layer, with ``x`` its normed input:

    [z, xBC, dt] = x W_in;  xBC <- silu(conv4(xBC) + b), never across an
    episode boundary;  [u, B, C] = xBC;  delta = softplus(dt + dt_bias);
    a = exp(-exp(A_log) delta);  S <- a S + delta u B^T;  y = S C + D u;
    out = RMSNorm(y * silu(z)) W_out

The carry's entries: a Mamba layer's ``{"S" [B, H, P, N] float32, "conv"
[B, 3, H P + 2 N] float32, "fresh" [B] bool}`` (the state zero where an
episode starts, as KDA's is: ``fresh`` is spent on its next read); an
attention layer's ``{"k", "v" [B, L, Hkv * dh], "len" [B] int32}``.

Precision: operands of the matrix products in ``compute_dtype``; the state,
decays, softplus, the conv and its bias, norms, softmax and the head's
log-softmax in float32; the cache's rows in ``compute_dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from asyncrl_tpu.models.lfm2_moe import _gqa_fragment, _gqa_step
from asyncrl_tpu.models.seq_common import (
    F32,
    SeqCore,
    SeqPolicyBase,
    _dot,
    _rms_norm,
    _short_conv,
    _zero_where,
    seeded,
)
from asyncrl_tpu.ops import ssd


@dataclasses.dataclass(frozen=True)
class GraniteHShape:
    """Published widths and the cut: what ``Config.seq_model`` names."""

    hidden: int
    vocab: int  # the held slice
    layers: tuple[str, ...]  # "mamba+dense" | "gqa+dense"
    heads: int
    kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    mamba_state: int
    ffn: int
    max_positions: int  # the K/V cache's capacity = the episode cap
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rope_theta: float | None = None  # NoPE
    conv_width: int = 4
    chunk: int = 256  # of the chunked scan
    eps: float = 1e-5
    # The learner runs a layer over this many tokens at a time (whole envs).
    block_tokens: int = 1024


_PERIOD = ("mamba+dense",) * 5 + ("gqa+dense",) + ("mamba+dense",) * 4

SHAPES: dict[str, GraniteHShape] = {
    # granite-4.0-h-micro's config.json at its published widths: layers 0-9
    # of 40 (one whole period: nine Mamba layers and the attention layer at
    # index 5), an eighth of the vocabulary: one stage of a four-stage
    # pipeline, the tied embedding over eight chips.
    "granite_h_10l": GraniteHShape(
        hidden=2048, vocab=12544, layers=_PERIOD,
        heads=32, kv_heads=8, head_dim=64,
        mamba_heads=64, mamba_head_dim=64, mamba_state=128, ffn=8192,
        max_positions=2048, attention_multiplier=0.015625,
        embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
        # 4 envs a block (compiled for a described v5e: PERF.md)
        block_tokens=1024,
    ),
    # CPU tests: both kinds of layer at toy widths; chunks of 8 in
    # fragments of 16, so episodes end inside chunks and across them.
    "granite_h_tiny": GraniteHShape(
        hidden=64, vocab=64, layers=("mamba+dense", "gqa+dense", "mamba+dense"),
        heads=4, kv_heads=2, head_dim=16,
        mamba_heads=4, mamba_head_dim=8, mamba_state=16, ffn=96,
        max_positions=32, attention_multiplier=0.015625,
        embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
        chunk=8, block_tokens=128,
    ),
}


# ------------------------------------------------------------------ mixers


def _mamba(p, x, state, done, shape: GraniteHShape, dtype):
    """``x`` [B, D] with ``done`` None (one token) or [T, B, D]. Returns (y,
    the layer's state, counters)."""
    H, P, N = shape.mamba_heads, shape.mamba_head_dim, shape.mamba_state
    inner = H * P
    with jax.named_scope("mamba"):
        z, xbc, dt = jnp.split(_dot(x, p["in"], dtype), [inner, 2 * inner + 2 * N], axis=-1)
        xbc, conv = _short_conv(p["conv"], state["conv"], xbc, done)
        u, B, C = jnp.split(jax.nn.silu(xbc + p["conv_bias"]), [inner, inner + N], axis=-1)
        u = u.reshape(*x.shape[:-1], H, P)
        delta = jax.nn.softplus(dt + p["dt_bias"])
        log_a = -jnp.exp(p["A_log"]) * delta
        fresh, seen = state["fresh"], {}
        if done is None:
            S, y = ssd.ssd_step(state["S"], u, delta, log_a, B, C, fresh)
        else:
            S, y = ssd.ssd_chunk(
                _zero_where(fresh, state["S"]), u, delta, log_a, B, C, done,
                chunk=shape.chunk, dtype=dtype,
            )
            inside, chunks = ssd.chunk_boundaries(done, shape.chunk)
            seen = {"ssd_chunk_resets": inside, "ssd_chunks": chunks}
        y = (y + p["D"][:, None] * u).reshape(*x.shape[:-1], inner)
        y = _rms_norm(y * jax.nn.silu(z), p["norm"], shape.eps)
        return _dot(y, p["out"], dtype), {
            "S": S, "conv": conv, "fresh": jnp.zeros_like(fresh)}, seen


# ------------------------------------------------------------------- model


@dataclasses.dataclass(frozen=True)
class GraniteHPolicy(SeqPolicyBase):
    """See the module docstring and ``seq_common.SeqPolicyBase``."""

    shape: GraniteHShape
    compute_dtype: Any = F32

    def initial_core(self, batch_size: int) -> SeqCore:
        s = self.shape
        rows = (batch_size, s.max_positions, s.kv_heads * s.head_dim)
        xbc = s.mamba_heads * s.mamba_head_dim + 2 * s.mamba_state
        return SeqCore(tuple(
            {"S": jnp.zeros((batch_size, s.mamba_heads, s.mamba_head_dim,
                             s.mamba_state), F32),
             "conv": jnp.zeros((batch_size, s.conv_width - 1, xbc), F32),
             "fresh": jnp.zeros((batch_size,), bool)}
            if kind.startswith("mamba") else
            {"k": jnp.zeros(rows, self.compute_dtype),
             "v": jnp.zeros(rows, self.compute_dtype),
             "len": jnp.zeros((batch_size,), jnp.int32)}
            for kind in s.layers
        ))

    def init(self, key, obs=None, core=None):
        """Seeded random weights: projections N(0, 1/fan_in), the embedding
        N(0, 0.1^2) (the family's ``initializer_range``: the tied head's
        logits then spread ~0.6 after the scaling), unit norms; Mamba-2's
        ``A_log`` = log U(1, 16), ``dt_bias`` the inverse softplus of a
        log-uniform step in [1e-3, 1e-1], ``D`` = 1, the conv's weights and
        bias as a ``Conv1d``'s default (U(+-1/sqrt(4)) for the bias)."""
        s = self.shape
        w, keys = seeded(key, 16 * (len(s.layers) + 1))
        D, H = s.hidden, s.mamba_heads
        inner = H * s.mamba_head_dim
        xbc = inner + 2 * s.mamba_state
        bound = 1.0 / math.sqrt(s.conv_width)
        params = {"embed": 0.1 * jax.random.normal(next(keys), (s.vocab, D), F32)}
        for i, kind in enumerate(s.layers):
            layer = {"norm_mixer": jnp.ones((D,), F32), "norm_ffn": jnp.ones((D,), F32),
                     "ffn": {"gate": w(D, s.ffn), "up": w(D, s.ffn), "down": w(s.ffn, D)}}
            if kind.startswith("mamba"):
                step = jnp.exp(jax.random.uniform(
                    next(keys), (H,), F32, math.log(1e-3), math.log(1e-1)))
                layer["mamba"] = {
                    "in": w(D, 2 * inner + 2 * s.mamba_state + H),  # z, xBC, dt
                    "conv": w(s.conv_width, xbc, fan_in=s.conv_width),
                    "conv_bias": jax.random.uniform(next(keys), (xbc,), F32, -bound, bound),
                    "A_log": jnp.log(jax.random.uniform(next(keys), (H,), F32, 1.0, 16.0)),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "D": jnp.ones((H,), F32),
                    "norm": jnp.ones((inner,), F32),
                    "out": w(inner, D),
                }
            else:
                n_q, n_kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
                layer["gqa"] = {"q": w(D, n_q), "k": w(D, n_kv), "v": w(D, n_kv),
                                "o": w(n_q, D)}
            params[f"layer_{i}"] = layer
        params["final_norm"] = jnp.ones((D,), F32)
        params["value"] = {"kernel": w(D, 1), "bias": jnp.zeros((1,), F32)}
        return {"params": params}

    def _mixer(self, p, mixer, x, state, done):
        s, dtype = self.shape, self.compute_dtype
        if mixer == "mamba":
            return _mamba(p, x, state, done, s, dtype)
        if done is None:
            return (*_gqa_step(p, x, state, s, dtype), {})
        return _gqa_fragment(p, x, state, done, s, dtype)
