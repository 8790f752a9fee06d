"""A token-level recurrent policy of the Kimi-Linear family: KDA (gated
delta-rule linear attention, short conv 4) and NoPE MLA (latent attention)
mixers, a leading dense feed-forward and routed-expert layers of which this
chip holds a share, an output head over the held vocabulary slice and a
value head (the RL addition).

One function in two forms (``docs/ARCHITECTURE.md`` "Sequence policy"):

- ``apply(params, tokens [B], core) -> (logits [B, V], value [B], core)``:
  one token through the carry, the rollout's form. The CALLER resets the
  carry where an episode ends (``models.networks.reset_core``) and settles
  it before anything but the policy reads it (``settle_core``).
- ``apply(params, tokens [T, B], done [T, B], core, actions [T, B],
  method="fragment") -> (logp, entropy, values [T, B], core, aux)``: the
  same function over a whole fragment from the fragment-initial carry, the
  learner's form: projections over all T*B tokens at once, KDA chunkwise,
  MLA under an episode mask, the head in token blocks (the [T*B, V] float32
  logits are never whole), resets applied inside, every block rematerialised
  in the backward pass. ``actions=None`` returns the logits instead (tests).

The carry (``SeqCore``) is a tuple with one entry per layer: a KDA layer's
``{"S" [B, H, dk, dv] float32, "conv" [B, 3, 3*H*dk], "fresh" [B] bool}``,
an MLA layer's ``{"kv" [B, L, kv_lora + rope], "len" [B] int32}`` -- two
kinds of state in one pytree, every leaf with the env axis first. A reset
does not pass over ``S`` (134 MB a layer at the published widths): it sets
``fresh``, and the next read of ``S``, in either form, takes zero there, as
``len`` empties a latent cache whose rows stay. ``settle()`` spends the
pending resets; a carry that leaves ``rollout.anakin.unroll`` or the
fragment form is settled (``docs/ARCHITECTURE.md`` "Sequence policy").

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
from flax import struct

from asyncrl_tpu.ops import kda, moe

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SeqShape:
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


@struct.dataclass
class SeqCore:
    """The carry: ``layers[i]`` is layer i's state."""

    layers: tuple

    def reset(self, done):
        """The carry the next token starts from: zero where ``done`` [B].
        A latent cache is emptied by its length and a KDA state by
        ``fresh``, both on their next read: the rows and the state stay."""
        with jax.named_scope("core_reset"):
            return SeqCore(tuple(
                {**layer, "len": _zero_where(done, layer["len"])}
                if "kv" in layer else
                {**layer, "conv": _zero_where(done, layer["conv"]),
                 "fresh": layer["fresh"] | done}
                for layer in self.layers
            ))

    def settle(self):
        """The same carry with no reset pending: ``S`` zero where ``fresh``,
        ``fresh`` all false. For whoever reads ``"S"`` and is neither form
        of the mixer (the learner's ``S0`` is safe either way)."""
        with jax.named_scope("core_reset"):
            return SeqCore(tuple(
                layer if "kv" in layer else
                {**layer, "S": _zero_where(layer["fresh"], layer["S"]),
                 "fresh": jnp.zeros_like(layer["fresh"])}
                for layer in self.layers
            ))


def _zero_where(done, x):
    return jnp.where(
        done.reshape(-1, *([1] * (x.ndim - 1))), jnp.zeros_like(x), x)


# ------------------------------------------------------------------ pieces


def _dot(x, kernel, dtype):
    return jnp.matmul(
        x.astype(dtype), kernel.astype(dtype), preferred_element_type=F32
    )


def _rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _swiglu(p, x, dtype):
    h = jax.nn.silu(_dot(x, p["gate"], dtype)) * _dot(x, p["up"], dtype)
    return _dot(h, p["down"], dtype)


def _short_conv(weights, tail, x, done):
    """Depthwise causal conv over time that never reads across an episode
    boundary. ``x`` [T, B, N] (or [B, N]: one token, ``done`` None);
    ``tail`` [B, W-1, N] the inputs before it. Returns (y, new tail)."""
    if x.ndim == 2:
        window = jnp.concatenate([tail, x[:, None]], axis=1)
        return jnp.einsum("bwn,wn->bn", window, weights), window[:, 1:]
    W, T = weights.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.moveaxis(tail, 1, 0), x], axis=0)  # [W-1+T, B, N]
    alive = jnp.concatenate(
        [jnp.ones((W - 1, x.shape[1]), F32), 1.0 - done.astype(F32)], axis=0
    )
    y, valid = weights[W - 1] * x, jnp.ones_like(alive[:T])
    for s in range(1, W):  # the input s tokens back, if no boundary since
        valid = valid * alive[W - 1 - s: W - 1 - s + T]
        y = y + weights[W - 1 - s] * ext[W - 1 - s: W - 1 - s + T] * valid[..., None]
    keep = jnp.cumprod(alive[T:][::-1], axis=0)[::-1]  # no boundary up to the end
    return y, jnp.moveaxis(ext[T:] * keep[..., None], 0, 1)


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


def _mla_project(p, x, shape: SeqShape, dtype):
    """Queries [..., H, nope + rope] and the latent row [..., lora + rope]
    (normed latent, then the shared unrotated key part) the cache holds."""
    q = _dot(x, p["q"], dtype).reshape(
        *x.shape[:-1], shape.mla_heads, shape.qk_nope + shape.qk_rope
    )
    kv = _dot(x, p["kv_a"], dtype)
    latent = jnp.concatenate([
        _rms_norm(kv[..., : shape.kv_lora], p["kv_norm"], shape.eps),
        kv[..., shape.kv_lora:],
    ], axis=-1)
    return q, latent.astype(dtype)


def _softmax(scores, mask):
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    e = jnp.where(mask, jnp.exp(scores), 0.0)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _mla_step(p, x, state, shape: SeqShape, dtype):
    """One token: write its latent row at ``len``, attend over the rows of
    the current episode with the up-projection absorbed into the query and
    the output (no per-position keys or values are formed)."""
    H, dn, lora = shape.mla_heads, shape.qk_nope, shape.kv_lora
    with jax.named_scope("mla"):
        q, latent = _mla_project(p, x, shape, dtype)
        B = x.shape[0]
        cache = state["kv"].at[jnp.arange(B), state["len"]].set(latent)
        kv_b = p["kv_b"].reshape(lora, H, dn + shape.v_head).astype(dtype)
        q_lat = jnp.einsum(
            "bhd,lhd->bhl", q[..., :dn].astype(dtype), kv_b[..., :dn],
            preferred_element_type=F32,
        )
        scores = jnp.einsum(
            "bhl,bpl->bhp",
            jnp.concatenate([q_lat, q[..., dn:]], axis=-1).astype(dtype), cache,
            preferred_element_type=F32,
        ) / math.sqrt(dn + shape.qk_rope)
        mask = jnp.arange(cache.shape[1])[None, :] <= state["len"][:, None]
        probs = _softmax(scores, mask[:, None, :])
        ctx = jnp.einsum(
            "bhp,bpl->bhl", probs.astype(dtype), cache[..., :lora],
            preferred_element_type=F32,
        )
        out = jnp.einsum(
            "bhl,lhd->bhd", ctx.astype(dtype), kv_b[..., dn:],
            preferred_element_type=F32,
        )
        return (
            _dot(out.reshape(B, -1), p["o"], dtype),
            {"kv": cache, "len": state["len"] + 1},
        )


def _env_block(batch: int, per_env: int, limit: int = 1 << 26) -> int:
    """Largest divisor of ``batch`` whose block stays under ``limit``
    elements of ``per_env`` each."""
    best = 1
    for b in range(1, batch + 1):
        if batch % b == 0 and b * per_env <= limit:
            best = b
    return best


def _to_blocks(a, axis: int, n: int):
    """Split the env axis into ``n`` blocks, blocks leading."""
    shape = a.shape[:axis] + (n, a.shape[axis] // n) + a.shape[axis + 1:]
    return jnp.moveaxis(a.reshape(shape), axis, 0)


def _from_blocks(a, axis: int):
    a = jnp.moveaxis(a, 0, axis)
    return a.reshape(a.shape[:axis] + (-1,) + a.shape[axis + 2:])


def _mla_fragment(p, x, state, done, shape: SeqShape, dtype):
    """A fragment: keys and values materialised for the cached rows of the
    episode in progress and the fragment's own, causal softmax within the
    episode, in blocks of envs."""
    H, dn, lora = shape.mla_heads, shape.qk_nope, shape.kv_lora
    T, B, _ = x.shape
    L = state["kv"].shape[1]
    with jax.named_scope("mla"):
        q, latent = _mla_project(p, x, shape, dtype)
        rows = jnp.concatenate(
            [state["kv"], jnp.moveaxis(latent, 0, 1)], axis=1
        )  # [B, L + T, lora + rope]
        ends = jnp.cumsum(done.astype(jnp.int32), axis=0)
        seg = (ends - done.astype(jnp.int32)).T  # [B, T] boundaries before t
        t = jnp.arange(T)
        mask = jnp.concatenate([
            (jnp.arange(L)[None, None, :] < state["len"][:, None, None])
            & (seg == 0)[:, :, None],
            (t[None, :, None] >= t[None, None, :])
            & (seg[:, :, None] == seg[:, None, :]),
        ], axis=-1)  # [B, T, L + T]

        def attend(args):
            q, rows, mask = args  # [b, T, H, dn + rope], [b, L+T, .], [b, T, L+T]
            kv = _dot(rows[..., :lora], p["kv_b"], dtype).reshape(
                *rows.shape[:2], H, dn + shape.v_head
            )
            scores = jnp.einsum(
                "bthd,bphd->bhtp", q[..., :dn].astype(dtype),
                kv[..., :dn].astype(dtype), preferred_element_type=F32,
            ) + jnp.einsum(
                "bthr,bpr->bhtp", q[..., dn:].astype(dtype), rows[..., lora:],
                preferred_element_type=F32,
            )
            probs = _softmax(
                scores / math.sqrt(dn + shape.qk_rope), mask[:, None]
            )
            return jnp.einsum(
                "bhtp,bphd->bthd", probs.astype(dtype),
                kv[..., dn:].astype(dtype), preferred_element_type=F32,
            )

        n = B // _env_block(B, H * T * (L + T))
        out = jax.lax.map(
            jax.checkpoint(attend),
            tuple(
                _to_blocks(a, 0, n) for a in (jnp.moveaxis(q, 0, 1), rows, mask)
            ),
        ).reshape(B, T, -1)
        out = _dot(jnp.moveaxis(out, 0, 1), p["o"], dtype)

        # the cache the next fragment starts from: the rows of the episode
        # in progress, from position 0 (rows past ``len`` are never read)
        any_done = ends[-1] > 0
        first = jnp.where(  # row of ``rows`` that lands at position 0
            any_done, L + T - 1 - jnp.argmax(done[::-1], axis=0) + 1, 0
        )
        length = jnp.where(any_done, L + T - first, state["len"] + T)
        pos = jnp.arange(L)[None, :]
        src = jnp.where(
            any_done[:, None] | (pos < state["len"][:, None]),
            first[:, None] + pos,
            L + pos - state["len"][:, None],
        )
        cache = jnp.take_along_axis(
            rows, jnp.clip(src, 0, L + T - 1)[..., None], axis=1
        )
        return out, {"kv": cache, "len": length.astype(jnp.int32)}


def _ffn(p, kind, x, shape: SeqShape, dtype):
    """The layer's feed-forward on rows ``x`` [N, D]: (y, held-expert loads
    or None)."""
    if kind == "dense":
        return _swiglu(p, x, dtype), None
    with jax.named_scope("moe"):
        ids, weights = moe.route(
            x, p["router"], p["router_bias"], shape.top_k, shape.routed_scale
        )
        y, load = moe.held_experts(
            x, ids, weights, shape.held_experts, shape.num_experts,
            p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"], dtype,
        )
        return y + _swiglu(p["shared"], x, dtype), load


# ------------------------------------------------------------------- model


@dataclasses.dataclass(frozen=True)
class SeqPolicy:
    """See the module docstring. Not a flax module: ``init`` / ``apply``
    over a plain nested dict, which is all the learner asks of a model."""

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
        keys = iter(jax.random.split(key, 64 * (len(s.layers) + 1)))

        def w(*dims, fan_in=None):
            std = (fan_in or dims[-2]) ** -0.5
            return std * jax.random.normal(next(keys), dims, F32)

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
                layer["mla"] = {
                    "q": w(D, s.mla_heads * (s.qk_nope + s.qk_rope)),
                    "kv_a": w(D, s.kv_lora + s.qk_rope),
                    "kv_norm": jnp.ones((s.kv_lora,), F32),
                    "kv_b": w(s.kv_lora, s.mla_heads * (s.qk_nope + s.v_head)),
                    "o": w(s.mla_heads * s.v_head, D),
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
                    "shared": swiglu(s.expert_ffn),
                }
            params[f"layer_{i}"] = layer
        params["final_norm"] = jnp.ones((D,), F32)
        params["head"] = w(D, s.vocab)
        params["value"] = {"kernel": w(D, 1), "bias": jnp.zeros((1,), F32)}
        return {"params": params}

    def apply(self, variables, *args, method: str | None = None):
        return getattr(self, method or "step")(variables["params"], *args)

    def _layer(self, p, kind, h, state, done):
        s, dtype = self.shape, self.compute_dtype
        mixer, ffn = kind.split("+")
        x = _rms_norm(h, p["norm_mixer"], s.eps)
        if mixer == "kda":
            y, state = _kda_mixer(p["kda"], x, state, done, s, dtype)
        elif done is None:
            y, state = _mla_step(p["mla"], x, state, s, dtype)
        else:
            y, state = _mla_fragment(p["mla"], x, state, done, s, dtype)
        h = h + y
        x = _rms_norm(h, p["norm_ffn"], s.eps)
        y, load = _ffn(p["ffn"], ffn, x.reshape(-1, s.hidden), s, dtype)
        return h + y.reshape(h.shape), state, load

    def _trunk(self, params, tokens, core, done):
        """Embedding and layers -> (final normed hidden, carry, loads)."""
        s = self.shape
        h = jnp.take(params["embed"], tokens, axis=0)
        states, loads = [], []
        for i, kind in enumerate(s.layers):
            p, state = params[f"layer_{i}"], core.layers[i]
            if done is None:
                h, state, load = self._layer(p, kind, h, state, None)
            else:
                # in blocks of whole envs, each rematerialised in the
                # backward pass: what is kept of a layer is its input
                n = tokens.shape[1] // _env_block(
                    tokens.shape[1], tokens.shape[0], s.block_tokens
                )
                h, state, load = jax.lax.map(
                    jax.checkpoint(
                        lambda a, p=p, kind=kind: self._layer(p, kind, *a)
                    ),
                    (_to_blocks(h, 1, n),
                     jax.tree.map(lambda c: _to_blocks(c, 0, n), state),
                     _to_blocks(done, 1, n)),
                )
                h = _from_blocks(h, 1)
                state = jax.tree.map(lambda c: _from_blocks(c, 0), state)
                load = None if load is None else jnp.sum(load, axis=0)
            states.append(state)
            if load is not None:
                loads.append(load)
        h = _rms_norm(h, params["final_norm"], s.eps)
        return h, SeqCore(tuple(states)), loads

    def _value(self, params, h):
        v = _dot(h, params["value"]["kernel"], self.compute_dtype)
        return v[..., 0] + params["value"]["bias"][0]

    def step(self, params, tokens, core):
        h, core, _ = self._trunk(params, tokens, core, None)
        with jax.named_scope("lm_head"):
            logits = _dot(h, params["head"], self.compute_dtype)
        return logits, self._value(params, h), core

    def fragment(self, params, tokens, done, core, actions=None):
        T, B = tokens.shape
        h, core, loads = self._trunk(params, tokens, core, done)
        values = self._value(params, h)
        core = core.reset(done[-1]).settle()
        loads = jnp.stack(loads).astype(F32)  # [expert layers, held]
        aux = {
            "moe_load_max": jnp.max(loads),
            "moe_load_mean": jnp.mean(loads),
            "moe_local_frac": jnp.sum(loads) / (
                loads.shape[0] * T * B * self.shape.top_k
            ),
            "episode_resets": jnp.sum(done.astype(F32)),
        }
        if actions is None:
            with jax.named_scope("lm_head"):
                return _dot(h, params["head"], self.compute_dtype), values, core, aux
        n = T * B
        b = _env_block(n, 1, 2048)

        def head(args):
            # the scope inside the mapped body: its backward ops keep it
            with jax.named_scope("lm_head"):
                h, a = args
                logits = _dot(h, params["head"], self.compute_dtype)
                logp = jax.nn.log_softmax(logits, axis=-1)
                taken = jnp.take_along_axis(logp, a[:, None], axis=-1)[:, 0]
                return taken, -jnp.sum(jnp.exp(logp) * logp, axis=-1)

        logp, entropy = jax.lax.map(
            jax.checkpoint(head),
            (h.reshape(n // b, b, -1),
             actions.astype(jnp.int32).reshape(n // b, b)),
        )
        return logp.reshape(T, B), entropy.reshape(T, B), values, core, aux
