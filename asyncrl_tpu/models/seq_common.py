"""What the token-level sequence policies share (``models/kimi_linear.py``,
``models/lfm2_moe.py``, ``models/keye_moe.py``, ``models/moonlight.py``,
``models/granite_h.py``; the latent-attention mixer two of them run is
``models/mla.py``): the carry and its reset-on-read protocol, the trunk
(embedding, layers in blocks of whole envs, each rematerialised; a shape
scales the embedding, each residual branch and the logits by its
``embedding_multiplier``, ``residual_multiplier`` and ``logits_scaling``,
1 on a record built on ``TrunkScales``), the feed-forward of a layer
(dense, or the routed experts this chip holds, scored by sigmoid or by
softmax), the
blocked output head (``params["head"]``, or the embedding where there is
none: a tied head), the value head, the fragment form's counters and the
model's own loss term, and the pieces a mixer is made of (norms, the
boundary-aware short conv, the rotation and the grouped-query projection,
the episode mask and the cache a fragment leaves).

A policy is ``SeqPolicyBase`` with a shape record of its own and three
methods: ``initial_core``, ``init`` and ``_mixer``. One function in two
forms (``docs/ARCHITECTURE.md`` "Sequence policy"):

- ``apply(params, tokens [B], core) -> (logits [B, V], value [B], core)``:
  one token through the carry, the rollout's form. The CALLER resets the
  carry where an episode ends (``models.networks.reset_core``) and settles
  it before anything but the policy reads it (``settle_core``).
- ``apply(params, tokens [T, B], done [T, B], core, actions [T, B],
  method="fragment") -> (logp, entropy, values [T, B], core, aux)``: the
  same function over a whole fragment from the fragment-initial carry, the
  learner's form: every layer over blocks of whole envs, the head in token
  blocks (the [T*B, V] float32 logits are never whole), resets applied
  inside, every block rematerialised in the backward pass. ``actions=None``
  returns the logits instead (tests).

The carry (``SeqCore``) is a tuple with one entry per layer, every leaf with
the env axis first, and six kinds of state live in it side by side:

- a KDA layer's ``{"S" [B, H, dk, dv] float32, "conv" [B, W-1, 3 H dk],
  "fresh" [B] bool}``;
- a latent-attention layer's ``{"kv" [B, L, lora + rope], "len" [B]}``;
- a gated short-conv layer's ``{"conv" [B, W-1, D] float32}``;
- a grouped-query attention layer's ``{"k", "v" [B, L, Hkv * dh], "len"
  [B]}`` (the keys rotated at their positions in the episode);
- a sparse-attention layer's ``{"k", "v" [B, L, Hkv * dh], "ki" [B, L, dI],
  "len" [B]}``: the same cache and a third kind of row beside it, the
  indexer's key of each position (``ops/dsa.py``). Every array of rows a
  cache holds is emptied by the one ``len`` and re-gathered by the one
  ``_cache_after``;
- a Mamba-2 layer's ``{"S" [B, H, P, N] float32, "conv" [B, W-1, H P + 2 N]
  float32, "fresh" [B] bool}``: a state-space state of a fixed size
  whatever the episode's length (``ops/ssd.py``), reset as KDA's is.

A model's own loss term (the sparse-attention indexer's KL term, which is
all that trains the indexer) leaves the fragment form in ``aux`` under
``MODEL_LOSS``; ``learn/learner.py`` adds it to the algorithm's loss.

A reset decides by what a layer's state holds: a conv tail is zeroed, a
cache is emptied by its ``len`` (the rows stay), and a KDA or Mamba-2 state
is not passed over at all (134 MB a layer at KDA's published widths): the
reset sets ``fresh``, and the next read of ``S``, in either form, takes zero
there.
``settle()`` spends the pending resets; a carry that leaves
``rollout.anakin.unroll`` or the fragment form is settled.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from asyncrl_tpu.ops import moe

F32 = jnp.float32
# ``aux``'s key for a loss term of the model's own: the learner adds it to
# the algorithm's loss and leaves it out of the metrics.
MODEL_LOSS = "model_loss"


@struct.dataclass
class SeqCore:
    """The carry: ``layers[i]`` is layer i's state."""

    layers: tuple

    def reset(self, done):
        """The carry the next token starts from: zero where ``done`` [B].
        A conv tail is zeroed; a cache is emptied by its length and a KDA
        state by ``fresh``, both on their next read: the rows and the state
        stay."""
        with jax.named_scope("core_reset"):
            return SeqCore(tuple(_reset(layer, done) for layer in self.layers))

    def settle(self):
        """The same carry with no reset pending: ``S`` zero where ``fresh``,
        ``fresh`` all false. For whoever reads ``"S"`` and is neither form
        of the mixer (the learner's ``S0`` is safe either way)."""
        with jax.named_scope("core_reset"):
            return SeqCore(tuple(
                {**layer, "S": _zero_where(layer["fresh"], layer["S"]),
                 "fresh": jnp.zeros_like(layer["fresh"])}
                if "fresh" in layer else layer
                for layer in self.layers
            ))


def _reset(layer: dict, done) -> dict:
    out = dict(layer)
    for name in ("len", "conv"):
        if name in layer:
            out[name] = _zero_where(done, layer[name])
    if "fresh" in layer:
        out["fresh"] = layer["fresh"] | done
    return out


def _zero_where(done, x):
    return jnp.where(
        done.reshape(-1, *([1] * (x.ndim - 1))), jnp.zeros_like(x), x)


# ------------------------------------------------------------------ pieces


def _dot(x, kernel, dtype):
    return jnp.matmul(
        x.astype(dtype), kernel.astype(dtype), preferred_element_type=F32
    )


def _rms_norm(x, scale, eps):
    # The barrier makes the sum of squares a pass of its own. Fused into
    # the epilogue of the product that made ``x`` it is summed in the order
    # of that product's output tiles, which XLA chooses by what else the
    # program holds in VMEM: the rollout inside the step and the same
    # rollout alone then differ in a float32's last bits, and sample other
    # tokens (PERF.md, PR 30).
    x = jax.lax.optimization_barrier(x.astype(F32))
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


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


def _rotate(x, pos, theta: float):
    """Rotary embedding on all of the last dim, rotate-half pairing
    (``x1 = x[..., :d/2]``, ``x2 = x[..., d/2:]``): ``x`` [..., H, d] at
    positions ``pos`` [...], float32."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = pos.astype(F32)[..., None, None] * freqs
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _gqa_project(p, x, pos, shape, dtype):
    """Queries [..., H, dh] and the key and value rows [..., Hkv * dh] the
    cache holds: projected, q and k normed over each head where ``p`` has
    the norms, then rotated at ``pos`` [...] where ``shape.rope_theta`` is
    not None (None: NoPE). ``shape``: ``heads``, ``kv_heads``, ``head_dim``,
    ``eps``, ``rope_theta`` and ``attention_multiplier``, the softmax's scale
    where it is not None (``TrunkScales``): folded into the queries, since
    the attention divides its scores by sqrt(dh)."""
    H, G, dh = shape.heads, shape.kv_heads, shape.head_dim

    def norm_and_rotate(t, norm):
        if norm in p:
            t = _rms_norm(t, p[norm], shape.eps)
        return t if shape.rope_theta is None else _rotate(t, pos, shape.rope_theta)

    q = _dot(x, p["q"], dtype).reshape(*x.shape[:-1], H, dh)
    k = _dot(x, p["k"], dtype).reshape(*x.shape[:-1], G, dh)
    q = norm_and_rotate(q, "q_norm")
    k = norm_and_rotate(k, "k_norm")
    if shape.attention_multiplier is not None:
        q = q * (shape.attention_multiplier * math.sqrt(dh))
    return (q, k.reshape(*x.shape[:-1], G * dh).astype(dtype),
            _dot(x, p["v"], dtype).astype(dtype))


def _softmax(scores, mask):
    scores = jnp.where(mask, scores, -jnp.inf)
    scores = scores - jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    e = jnp.where(mask, jnp.exp(scores), 0.0)
    return e / jnp.sum(e, axis=-1, keepdims=True)


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


def _episode_mask(done, length, L: int):
    """Which rows a fragment's token may attend, of the ``L`` cached rows
    of the episode in progress followed by the fragment's own ``T``: those
    of its own episode up to itself. ``done`` [T, B], ``length`` [B] the
    rows the cache holds. Returns (mask [B, T, L + T], the running count of
    boundaries [T, B])."""
    T = done.shape[0]
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0)
    seg = (ends - done.astype(jnp.int32)).T  # [B, T] boundaries before t
    t = jnp.arange(T)
    mask = jnp.concatenate([
        (jnp.arange(L)[None, None, :] < length[:, None, None])
        & (seg == 0)[:, :, None],
        (t[None, :, None] >= t[None, None, :])
        & (seg[:, :, None] == seg[:, None, :]),
    ], axis=-1)
    return mask, ends


def _cache_after(done, ends, length, L: int):
    """The cache the next fragment starts from holds the rows of the
    episode in progress, from position 0 (rows past ``len`` are never
    read). Returns (for each of its ``L`` rows the row of ``[cache,
    fragment]`` it is taken from [B, L], its length [B])."""
    T = done.shape[0]
    any_done = ends[-1] > 0
    first = jnp.where(  # row of ``rows`` that lands at position 0
        any_done, L + T - 1 - jnp.argmax(done[::-1], axis=0) + 1, 0
    )
    new_length = jnp.where(any_done, L + T - first, length + T)
    pos = jnp.arange(L)[None, :]
    src = jnp.where(
        any_done[:, None] | (pos < length[:, None]),
        first[:, None] + pos,
        L + pos - length[:, None],
    )
    return jnp.clip(src, 0, L + T - 1), new_length.astype(jnp.int32)


def seeded(key, n: int):
    """``w(*dims, fan_in=None)``: the next of ``n`` seeded N(0, 1/fan_in)
    matrices (fan-in: the second-to-last dim), and the iterator of keys it
    draws from."""
    keys = iter(jax.random.split(key, n))

    def w(*dims, fan_in=None):
        std = (fan_in or dims[-2]) ** -0.5
        return std * jax.random.normal(next(keys), dims, F32)

    return w, keys


# ------------------------------------------------------------------- model


class TrunkScales:
    """The trunk's multipliers at their neutral values, the base of a shape
    record that states none of them: class attributes, not dataclass fields,
    so such a record's fields are what they were. A record that states them
    (``models/granite_h.py``) declares the four as fields instead."""

    embedding_multiplier = 1
    residual_multiplier = 1
    logits_scaling = 1
    attention_multiplier = None  # the softmax's scale is 1/sqrt(head_dim)


@dataclasses.dataclass(frozen=True)
class SeqPolicyBase:
    """See the module docstring. Not a flax module: ``init`` / ``apply``
    over a plain nested dict, which is all the learner asks of a model.
    ``shape`` names its ``layers`` ("<mixer>+<dense|moe>"), ``hidden``,
    ``eps``, ``block_tokens`` and the expert layer's sizes."""

    shape: Any
    compute_dtype: Any = F32

    # added to the sum the router's chosen scores are renormalised by
    ROUTE_EPS = 0.0
    # how the router scores the experts (``ops/moe.py route``)
    ROUTE_SCORE = "sigmoid"

    def apply(self, variables, *args, method: str | None = None):
        return getattr(self, method or "step")(variables["params"], *args)

    def _mixer(self, p, mixer, x, state, done):
        """``x`` [B, D] with ``done`` None (one token) or [T, B, D] ->
        (y, the layer's state, counters {name: float32 scalar})."""
        raise NotImplementedError

    def _ffn(self, p, kind, x, steps=None):
        """The layer's feed-forward on rows ``x`` [N, D]: (y, counters: the
        held experts' loads and whether the block was computed densely;
        where ``steps`` is given, ``x``'s rows are [steps, envs] and
        ``step_hits`` [steps, held] counts the tokens of each step that
        chose each held expert)."""
        s, dtype = self.shape, self.compute_dtype
        if kind == "dense":
            return _swiglu(p, x, dtype), {}
        with jax.named_scope("moe"):
            ids, weights = moe.route(
                x, p["router"], p.get("router_bias"), s.top_k, s.routed_scale,
                self.ROUTE_EPS, self.ROUTE_SCORE,
            )
            y, load, dense = moe.held_experts(
                x, ids, weights, s.held_experts, s.num_experts,
                p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"],
                dtype,
            )
            if "shared" in p:
                y = y + _swiglu(p["shared"], x, dtype)
            counted = {"load": load, "dense": dense.astype(F32)}
            if steps is not None:
                hit = jnp.any(ids[:, :, None] == jnp.asarray(
                    s.held_experts, ids.dtype), axis=1)
                counted["step_hits"] = jnp.sum(
                    hit.reshape(steps, -1, hit.shape[-1]), axis=1, dtype=jnp.int32)
            return y, counted

    def _layer(self, p, kind, h, state, done, steps=None):
        """``steps``: where given, ``h`` is [steps, envs, D] and the expert
        layer counts ``step_hits`` (``_ffn``)."""
        s = self.shape
        mixer, ffn = kind.split("+")
        x = _rms_norm(h, p["norm_mixer"], s.eps)
        y, state, seen = self._mixer(p[mixer], mixer, x, state, done)
        h = h + self._residual(y)
        x = _rms_norm(h, p["norm_ffn"], s.eps)
        y, counted = self._ffn(p["ffn"], ffn, x.reshape(-1, s.hidden), steps)
        return h + self._residual(y.reshape(h.shape)), state, {**seen, **counted}

    def _residual(self, y):
        """What a layer's branch adds to the residual stream: ``y`` scaled by
        the shape's ``residual_multiplier``."""
        r = self.shape.residual_multiplier
        return y if r == 1 else r * y

    def _trunk(self, params, tokens, core, done):
        """Embedding and layers -> (final normed hidden, carry, each
        layer's counters summed over its blocks)."""
        s = self.shape
        h = jnp.take(params["embed"], tokens, axis=0)
        if s.embedding_multiplier != 1:
            h = h * s.embedding_multiplier
        states, counters = [], []
        for i, kind in enumerate(s.layers):
            p, state = params[f"layer_{i}"], core.layers[i]
            if done is None:
                h, state, counted = self._layer(p, kind, h, state, None)
            else:
                # in blocks of whole envs, each rematerialised in the
                # backward pass: what is kept of a layer is its input
                T, B = tokens.shape
                n = B // _env_block(B, T, s.block_tokens)
                # where the rollout's decode steps of B tokens compute only
                # the experts some token chose, count what they skip
                steps = T if kind.endswith("+moe") and moe.skips_idle(
                    B, s.top_k, s.num_experts) else None
                h, state, counted = jax.lax.map(
                    jax.checkpoint(
                        lambda a, p=p, kind=kind, steps=steps: self._layer(
                            p, kind, *a, steps=steps)
                    ),
                    (_to_blocks(h, 1, n),
                     jax.tree.map(lambda c: _to_blocks(c, 0, n), state),
                     _to_blocks(done, 1, n)),
                )
                h = _from_blocks(h, 1)
                state = jax.tree.map(lambda c: _from_blocks(c, 0), state)
                counted = {k: jnp.sum(v, axis=0) for k, v in counted.items()}
            states.append(state)
            counters.append(counted)
        h = _rms_norm(h, params["final_norm"], s.eps)
        return h, SeqCore(tuple(states)), counters

    def _value(self, params, h):
        v = _dot(h, params["value"]["kernel"], self.compute_dtype)
        return v[..., 0] + params["value"]["bias"][0]

    def _logits(self, params, h):
        """The output head: ``params["head"]``, or where there is none the
        embedding (a tied head); the logits divided by the shape's
        ``logits_scaling``."""
        if "head" in params:
            logits = _dot(h, params["head"], self.compute_dtype)
        else:
            logits = jnp.einsum(
                "...d,vd->...v", h.astype(self.compute_dtype),
                params["embed"].astype(self.compute_dtype),
                preferred_element_type=F32,
            )
        scale = self.shape.logits_scaling
        return logits if scale == 1 else logits / scale

    def step(self, params, tokens, core):
        h, core, _ = self._trunk(params, tokens, core, None)
        with jax.named_scope("lm_head"):
            logits = self._logits(params, h)
        return logits, self._value(params, h), core

    def fragment(self, params, tokens, done, core, actions=None):
        T, B = tokens.shape
        h, core, counters = self._trunk(params, tokens, core, done)
        values = self._value(params, h)
        core = core.reset(done[-1]).settle()
        aux = {}
        # [expert layers, held]; a model with no expert layer has no loads
        experts = any("load" in c for c in counters)
        if experts:
            loads = jnp.stack([c["load"] for c in counters if "load" in c]).astype(F32)
            aux.update(
                moe_load_max=jnp.max(loads),
                moe_load_mean=jnp.mean(loads),
                moe_local_frac=jnp.sum(loads) / (
                    loads.shape[0] * T * B * self.shape.top_k
                ),
            )
        aux["episode_resets"] = jnp.sum(done.astype(F32))
        if experts:
            # what the expert layers had to compute, and how many of the
            # update's blocks took the dense side to do it
            aux["moe_local_assignments"] = jnp.sum(loads)
            aux["moe_dense_blocks"] = sum(c["dense"] for c in counters if "dense" in c)
        hits = [c["step_hits"] for c in counters if "step_hits" in c]
        if hits:
            # the share of (expert layer, held expert, step) that no env's
            # token chose: what a decode step of the rollout skips
            aux["moe_step_idle_share"] = jnp.mean((jnp.stack(hits) == 0).astype(F32))
        attended = [c["rows_attended"] for c in counters if "rows_attended" in c]
        if attended:  # mean rows a query attended, over the attention layers
            aux["gqa_rows_attended"] = sum(attended) / (len(attended) * T * B)
        latent = [c for c in counters if "mla_rows_attended" in c]
        if latent:
            # means over the latent-attention layers: rows a query attended,
            # and an env's rows handed to the fragment form, rows computed
            # (its rung of the cache) and cached rows it needed
            n = len(latent)
            aux["mla_rows_attended"] = sum(
                c["mla_rows_attended"] for c in latent) / (n * T * B)
            for name in ("mla_rows_expanded", "mla_rows_computed", "mla_rows_cached"):
                aux[name] = sum(c[name] for c in latent) / (n * B)
        scans = [c for c in counters if "ssd_chunk_resets" in c]
        if scans:  # boundaries the chunked scans masked, a chunk (layers alike)
            aux["ssd_chunk_resets"] = jax.lax.stop_gradient(
                sum(c["ssd_chunk_resets"] for c in scans)
                / sum(c["ssd_chunks"] for c in scans))
        sparse = [c for c in counters if "indexer_kl" in c]
        if sparse:
            # the selection's counters, means over queries and sparse layers
            for name in ("dsa_rows_scored", "dsa_rows_selected", "dsa_pruned_share",
                         "dsa_rows_computed"):
                aux[name] = jax.lax.stop_gradient(
                    sum(c[name] for c in sparse) / (len(sparse) * T * B))
            # the indexers' loss: each layer's mean KL over the fragment's
            # queries, the layers' added (all that trains an indexer)
            aux[MODEL_LOSS] = sum(c["indexer_kl"] for c in sparse) / (T * B)
            aux["indexer_kl"] = jax.lax.stop_gradient(aux[MODEL_LOSS])
        if actions is None:
            with jax.named_scope("lm_head"):
                return self._logits(params, h), values, core, aux
        n = T * B
        b = _env_block(n, 1, 2048)

        def head(args):
            # the scope inside the mapped body: its backward ops keep it
            with jax.named_scope("lm_head"):
                h, a = args
                logits = self._logits(params, h)
                logp = jax.nn.log_softmax(logits, axis=-1)
                taken = jnp.take_along_axis(logp, a[:, None], axis=-1)[:, 0]
                return taken, -jnp.sum(jnp.exp(logp) * logp, axis=-1)

        logp, entropy = jax.lax.map(
            jax.checkpoint(head),
            (h.reshape(n // b, b, -1),
             actions.astype(jnp.int32).reshape(n // b, b)),
        )
        return logp.reshape(T, B), entropy.reshape(T, B), values, core, aux
