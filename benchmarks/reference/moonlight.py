"""Plain float32 ``jax.numpy`` reference of the ``moonlight_rl`` policy and its
loss: Moonlight-16B-A3B (``deepseek_v3``) as the published config and
DeepSeek-V3's published code give its layers, one chip's share of the
experts and of the vocabulary.

Independent of the code under test: nothing here imports ``asyncrl_tpu``; it
reads the program's parameters by name (``models/moonlight.py
MoonlightPolicy.init`` lists them) and the model's sizes from the
configuration file's ``model`` record; what it shares with the other plain
references of a token-level policy (products, norms, the rotate-half
rotation, the heads, V-trace and the loss's terms, positions from the
``done`` flags, a cache rebuilt from rows) it imports from
``reference/lfm2_moe.py`` and ``reference/keye_moe.py``. Every product runs
at ``Precision.HIGHEST``.

NO CACHE: the reference is given each env's tokens and ``done`` flags since
its caches were empty (the whole history, the fragment last) and one more
token, the bootstrap observation, and computes every layer over all of it.
A token's position is its index in its episode, counted from the flags.
Latent attention in its NON-ABSORBED form: every row of the history is
up-projected by ``kv_b`` into per-head keys ``[k_nope, rope(k_pe)]`` and
values, and a query attends every earlier row of its episode through them
(the program's one-token form absorbs ``kv_b`` into the query and the
output, so agreement holds the absorption too). The rotation is DeepSeek's:
adjacent dims are pairs; the published code permutes them to the half
layout and rotates there, frequencies ``theta^(-2i/64)``, no YaRN
(``rope_scaling`` null), the softmax scale ``(128 + 64)^-1/2``. The experts
by a loop over the held ids: sigmoid scores, the top 6 of score +
correction bias, the chosen scores renormalised and scaled; the two shared
experts as one SwiGLU of twice the expert width, as published. What a
cache would hold after any token is rebuilt from the rows (``carry_at``).

The program pairs the rope dims as rotate-half does, which is the published
rotation under a fixed permutation of the 64 rope columns of each head's
``q`` and of ``kv_a``: ``published`` gives the program's parameters in the
published order, ``program_order`` takes a gradient back.

Departures from the published model, all shared with the program: layers
0-4 of 27, the held experts' part of each routed layer only, the held slice
of the vocabulary, a value head (the RL addition), no sequence-wise
auxiliary balance loss (``seq_aux``) and no bias update (the correction
bias is a seeded buffer), no multi-token-prediction layer, seeded random
weights.

Wrong on purpose (``how``): ``rope=False`` (nothing rotated: Kimi-Linear's
NoPE), ``theta`` (another rotary base), ``shared`` (that many shared
experts of the expert width, of the published two), ``held`` (another set of
experts' parts added); ``low=True`` is the same computation in bfloat16
throughout, or ``low`` names some of ``reference/lfm2_moe.py PARTS``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference import plain
from benchmarks.reference.keye_moe import _einsum, carry_at, positions
from benchmarks.reference.lfm2_moe import (  # noqa: F401  (re-exported)
    BF16,
    F32,
    HIGHEST,
    TAIL,
    _entropy,
    _env_blocks,
    _is_low,
    _keep,
    _log_softmax,
    _loss_terms,
    _mm,
    _rms,
    _rope,
    _swiglu,
    _taken,
    heads,
    loss_of,
    tail_gradient,
)


# ----------------------------------------------------- the rope's two orders


def _half_order(d: int) -> jnp.ndarray:
    """Published rope column of each column of the half layout: the
    published code's ``view(d // 2, 2).transpose`` (evens, then odds)."""
    return jnp.concatenate([jnp.arange(0, d, 2), jnp.arange(1, d, 2)])


def rope_columns(p: dict, dims: dict) -> dict:
    """A latent-attention layer's rope columns: ``q``'s [D, H, rope] (each
    head's last ``qk_rope``) and ``kv_a``'s [D, rope]."""
    D, H, dn = dims["hidden"], dims["mla_heads"], dims["qk_nope"]
    return {"q": p["q"].reshape(D, H, -1)[..., dn:],
            "kv_a": p["kv_a"][:, dims["kv_lora"]:]}


def _with_rope_columns(p: dict, cols: dict, dims: dict) -> dict:
    D, H, dn = dims["hidden"], dims["mla_heads"], dims["qk_nope"]
    q = p["q"].reshape(D, H, -1)
    return {**p,
            "q": jnp.concatenate([q[..., :dn], cols["q"]], axis=-1).reshape(D, -1),
            "kv_a": jnp.concatenate([p["kv_a"][:, :dims["kv_lora"]], cols["kv_a"]],
                                    axis=-1)}


def published(variables: dict, dims: dict) -> dict:
    """The program's parameters with every layer's rope columns in the
    published (adjacent-pair) order."""
    back = jnp.argsort(_half_order(dims["qk_rope"]))
    params = dict(variables["params"])
    for i, _ in enumerate(dims["layers"]):
        layer = params[f"layer_{i}"]
        cols = {k: v[..., back] for k, v in rope_columns(layer["mla"], dims).items()}
        params[f"layer_{i}"] = {**layer, "mla": _with_rope_columns(layer["mla"], cols, dims)}
    return {**variables, "params": params}


def program_order(cols: dict, dims: dict) -> dict:
    """Rope columns (``rope_columns``' tree, e.g. a gradient) of the
    published order in the program's."""
    order = _half_order(dims["qk_rope"])
    return {k: v[..., order] for k, v in cols.items()}


def rope_published(x, pos, theta, low=False):
    """DeepSeek-V3's ``apply_rotary_pos_emb``: ``x`` [..., H, d] with
    adjacent dims as pairs, at positions ``pos`` [...]; the result in the
    half layout the published code rotates in."""
    return _rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1), pos, theta, low)


# ------------------------------------------------------------------- layers


def _latent(p, x, pos, dims, low, rope, theta):
    """Queries [N, b, H, nope + rope], the normed latent [N, b, lora] and
    the shared rope key [N, b, rope], rotated where ``rope``."""
    H, dn, lora = dims["mla_heads"], dims["qk_nope"], dims["kv_lora"]
    q = _mm(x, p["q"], low).reshape(*x.shape[:-1], H, -1)
    kv_a = _mm(x, p["kv_a"], low)
    c = _rms(kv_a[..., :lora], p["kv_norm"], dims["eps"], low)
    k_pe = kv_a[..., lora:]
    if rope:
        q = jnp.concatenate([q[..., :dn], rope_published(q[..., dn:], pos, theta, low)],
                            axis=-1)
        k_pe = rope_published(k_pe[..., None, :], pos, theta, low)[..., 0, :]
    return q, c, k_pe


def _keys_values(p, c, k_pe, dims, low):
    """Every row up-projected: keys [N, b, H, nope + rope] (the rope key
    shared by the heads) and values [N, b, H, v]."""
    H, dn = dims["mla_heads"], dims["qk_nope"]
    N, b, _ = c.shape
    kv = _mm(c, p["kv_b"], low).reshape(N, b, H, -1)
    keys = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (N, b, H, k_pe.shape[-1]))],
        axis=-1)
    return keys, kv[..., dn:]


def _attend(q, keys, values, valid, low):
    """``q`` [Q, b, H, dk] over every row of ``keys`` / ``values`` [N, b, H,
    .] that ``valid`` [b, Q, N] admits: [Q, b, H, dv]."""
    scores = _einsum("qbhd,sbhd->bhqs", q, keys, low) / math.sqrt(q.shape[-1])
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    if _is_low(low, "softmax"):
        scores = scores.astype(BF16)
    probs = jax.nn.softmax(scores, axis=-1).astype(F32)
    return _einsum("bhqs,sbhd->qbhd", probs, values, low)


def _valid(episode, episode_q, time_q, N):
    """[b, Q, N]: the rows of a query's episode up to itself."""
    return (episode.T[:, None, :] == episode_q.T[:, :, None]) & (
        jnp.arange(N)[None, None, :] <= time_q[None, :, None])


def mla_layer(p, x, pos, episode, dims, n_last, low=False, rope=True, theta=None):
    """``x`` [N, b, D], every token of the history and the bootstrap token
    last (N = whole blocks of ``n_last`` queries + 1), queries in blocks of
    ``n_last``. Returns (y [N, b, D], the rows a cache holds [N, b, lora +
    rope], the rows each of the last block's queries attended [b,
    n_last])."""
    N, b, _ = x.shape
    q, c, k_pe = _latent(p, x, pos, dims, low, rope, theta)
    keys, values = _keys_values(p, c, k_pe, dims, low)
    time = jnp.arange(N)

    def block(args):
        q, episode_q, time_q = args
        valid = _valid(episode, episode_q, time_q, N)
        return _attend(q, keys, values, valid, low), jnp.sum(valid, axis=-1)

    n = (N - 1) // n_last
    blocks = lambda a: a[:N - 1].reshape(n, n_last, *a.shape[1:])
    out, attended = jax.lax.map(block, tuple(blocks(a) for a in (q, episode, time)))
    out_boot, _ = block(tuple(a[N - 1:] for a in (q, episode, time)))
    out = jnp.concatenate([out.reshape(N - 1, *out.shape[2:]), out_boot], axis=0)
    y = _mm(out.reshape(N, b, -1), p["o"], low)
    return y, jnp.concatenate([c, k_pe], axis=-1), attended[-1]


def expert_layer(p, x, dims, low=False, held=None, shared=None):
    """``x`` [N, D] -> the shared experts + the held experts' weighted part:
    sigmoid scores in float32, the top k of score + correction bias (one
    group: ``noaux_tc`` with ``n_group`` 1), the chosen scores renormalised
    (``norm_topk_prob``) and scaled. ``shared``: how many shared experts of
    the expert width (default: all the configuration's)."""
    ids_held = list(dims["held_experts"])
    held = ids_held if held is None else list(held)
    logits = _mm(x, p["router"], low)
    if _is_low(low, "router"):
        logits = logits.astype(BF16)
    scores = jax.nn.sigmoid(logits).astype(F32)
    biased = scores + p["router_bias"]
    rank = jnp.argsort(jnp.argsort(-biased, axis=-1, stable=True), axis=-1)
    chosen = rank < dims["top_k"]
    total = jnp.sum(jnp.where(chosen, scores, 0.0), axis=-1, keepdims=True)
    weights = dims["routed_scale"] * jnp.where(chosen, scores, 0.0) / total
    width = dims["shared_ffn"] if shared is None else shared * dims["expert_ffn"]
    s = p["shared"]
    y = _swiglu({"gate": s["gate"][:, :width], "up": s["up"][:, :width],
                 "down": s["down"][:width]}, x, low)
    for row, expert in enumerate(ids_held):
        if expert not in held:
            continue
        e = {k: p["experts"][k][row] for k in ("gate", "up", "down")}
        y = y + weights[:, expert:expert + 1] * _swiglu(e, x, low)
    return y


def _ffn(p, kind, x, dims, low, held, shared):
    if kind.endswith("+dense"):
        return _swiglu(p, x, low)
    return expert_layer(p, x, dims, low, held, shared)


def trunk(variables, dims, tokens, done, n_last, low=False, held=None, rope=True,
          theta=None, shared=None):
    """``tokens``, ``done`` [N, b]: the history and the bootstrap token.
    Returns (the last layer's output [N, b, D], by layer the cache's rows,
    the rows the fragment's queries attended, and the layer's input and
    normed input)."""
    params = variables["params"]
    pos, episode = positions(done)
    theta = dims["rope_theta"] if theta is None else theta
    h = _keep(params["embed"].astype(F32)[tokens], low, "activations")
    rows, attended, inputs = [], [], []
    for i, kind in enumerate(dims["layers"]):
        p = params[f"layer_{i}"]
        x = _rms(h, p["norm_mixer"], dims["eps"], low)
        inputs.append((h, x))
        y, r, a = mla_layer(p["mla"], x, pos, episode, dims, n_last, low, rope, theta)
        h = _keep(h + y, low, "activations")
        rows.append(r)
        attended.append(a)
        x = _rms(h, p["norm_ffn"], dims["eps"], low).reshape(-1, h.shape[-1])
        y = _ffn(p["ffn"], kind, x, dims, low, held, shared)
        h = _keep(h + y.reshape(h.shape), low, "activations")
    return h, rows, attended, inputs


def _rope_loss(cols, variables, dims, last, inputs, pos, episode, fragment, low,
               rope, theta, held, shared):
    """The block's share of the loss, summed over its fragment's tokens, as
    a function of the last layer's rope columns ``cols``: the last layer
    again over the fragment's queries, the rows before the fragment
    constants (the program's cached rows are data), then its feed-forward
    and the heads; the V-trace targets constants."""
    params = variables["params"]
    layer = params[f"layer_{last}"]
    p = _with_rope_columns(layer["mla"], cols, dims)
    h_in, x = inputs
    N, b, _ = x.shape
    T = fragment["actions"].shape[0]
    first = N - 1 - T
    q, c, k_pe = _latent(p, x, pos, dims, low, rope, theta)
    k_pe = jnp.concatenate([jax.lax.stop_gradient(k_pe[:first]), k_pe[first:]])
    keys, values = _keys_values(p, c, k_pe, dims, low)
    valid = _valid(episode, episode[first:N - 1], jnp.arange(first, N - 1), N)
    out = _attend(q[first:N - 1], keys, values, valid, low)
    h = h_in[first:N - 1] + _mm(out.reshape(T, b, -1), p["o"], low)
    y = _ffn(layer["ffn"], dims["layers"][last],
             _rms(h, layer["norm_ffn"], dims["eps"], low).reshape(-1, h.shape[-1]),
             dims, low, held, shared)
    h = h + y.reshape(h.shape)
    logits, values = heads(params, dims, h, low)
    logp_all = _log_softmax(logits, low)
    return (-jnp.sum(_taken(logp_all, fragment["actions"]) * fragment["pg_adv"])
            + fragment["value_coef"] * 0.5 * jnp.sum(jnp.square(fragment["vs"] - values))
            - fragment["entropy_coef"] * jnp.sum(_entropy(logp_all)))


PARTS_OF_ROW = {"c_kv": lambda d: slice(0, d["kv_lora"]),
                "k_pe": lambda d: slice(d["kv_lora"], None)}


def carry_gap(mine: list, theirs: list, dims: dict):
    """One carry held to another, by layer: (sums of squares [layers, 2] of
    ``mine - theirs`` and of ``theirs`` over the rows up to ``theirs``'
    ``len``, the normed latent and the rope key apart; envs whose ``len``
    differs [layers])."""
    sq, ref, lens = [], [], []
    for a, b in zip(mine, theirs):
        live = (jnp.arange(b["kv"].shape[1])[None, :] < b["len"][:, None])[..., None]
        x, y = (jnp.where(live, r["kv"].astype(F32), 0.0) for r in (a, b))
        parts = [cut(dims) for cut in PARTS_OF_ROW.values()]
        sq.append(jnp.stack([jnp.sum(jnp.square(x[..., s] - y[..., s])) for s in parts]))
        ref.append(jnp.stack([jnp.sum(jnp.square(y[..., s])) for s in parts]))
        lens.append(jnp.sum(a["len"] != b["len"]))
    return jnp.stack(sq), jnp.stack(ref), jnp.stack(lens)


def carry_gaps(sq, ref, lens) -> dict:
    """``carry_gap``'s sums as readings, by layer: the largest ``|mine -
    theirs| / |theirs|`` of the latent and the rope key, each apart, and the
    envs whose ``len`` differs."""
    gaps = jnp.sqrt(sq / jnp.maximum(ref, 1e-30))
    return {"rows": jnp.max(gaps, axis=-1), "c_kv": gaps[:, 0], "k_pe": gaps[:, 1],
            "len": lens}


def evaluate(variables, dims, fragment, env_block: int, carries=None,
             carry_dtype=None, loss=None, low=False, **how):
    """One fragment seen through its history. ``variables`` in the
    published order (``published``). ``fragment``: ``history_obs``,
    ``history_done`` [Th, B] (the fragment's T steps last),
    ``bootstrap_obs`` [B], ``actions`` [T, B]; with ``loss`` (``gamma``,
    ``value_coef``, ``entropy_coef``, ``rho_clip``, ``c_clip``) also
    ``behaviour_logp``, ``rewards``, ``done`` [T, B]. In blocks of
    ``env_block`` envs. Returns a dict: ``logp``, ``entropy_of``,
    ``values``, ``hidden`` [T, B, ...], ``bootstrap_value`` [B],
    ``mla_rows_attended`` and ``mla_rows_cached`` (means over queries, over
    envs), ``core_before`` and ``core`` (by layer, the cache before and after
    the fragment, ``capacity`` rows, cast to ``carry_dtype`` if given) or,
    with ``carries`` (``{"before", "after"}``: another's caches by layer),
    instead of them ``carry_gaps`` (``{"before", "after"}``: ``carry_gaps``'
    readings; at the timed size sixteen envs' rebuilt caches in float32 are
    1.5 GB each, so they are held where they are rebuilt, an env block at a
    time); with ``loss`` ``rope_gradient``, d loss / d (the last layer's rope
    columns), published order."""
    T, B = fragment["actions"].shape
    Th = fragment["history_obs"].shape[0]
    n = B // env_block
    L = dims["max_positions"]
    last = len(dims["layers"]) - 1
    rope, theta = how.get("rope", True), how.get("theta") or dims["rope_theta"]
    held, shared = how.get("held"), how.get("shared")

    def block(args):
        obs, done, boot, actions, carried, extra = args  # env axis leading
        obs, done, actions = (jnp.moveaxis(a, 0, 1) for a in (obs, done, actions))
        tokens = jnp.concatenate([obs, boot[None]], axis=0)
        done = jnp.concatenate([done, jnp.zeros_like(done[:1])], axis=0)
        h, rows, attended, inputs = trunk(variables, dims, tokens, done, T, low, **how)
        pos, episode = positions(done)
        logits, values = heads(variables["params"], dims, h[Th - T:], low)
        logp_all = _log_softmax(logits[:T], low)
        out = {
            "logp": _taken(logp_all, actions), "entropy_of": _entropy(logp_all),
            "values": values[:T], "bootstrap_value": values[T], "hidden": h[Th - T:Th],
            "attended_sum": sum(jnp.sum(a) for a in attended) / len(attended),
            "cached_sum": jnp.sum(pos[Th - T]),
        }
        rebuilt = {"before": [carry_at({"kv": r}, pos, Th - T, L) for r in rows],
                   "after": [carry_at({"kv": r}, pos, Th, L) for r in rows]}
        if carried is not None:
            out["carry_gaps"] = {k: carry_gap(carried[k], rebuilt[k], dims)
                                 for k in rebuilt}
        else:
            cast = lambda c: {"kv": c["kv"].astype(carry_dtype or F32), "len": c["len"]}
            out["core_before"] = [cast(c) for c in rebuilt["before"]]
            out["core"] = [cast(c) for c in rebuilt["after"]]
        if loss is not None:
            behaviour, rewards, frag_done = (jnp.moveaxis(a, 0, 1) for a in extra)
            vs, pg_adv = plain.vtrace_sequential(
                behaviour.astype(F32), out["logp"], rewards.astype(F32),
                loss["gamma"] * (1.0 - frag_done.astype(F32)), out["values"],
                out["bootstrap_value"], loss["rho_clip"], loss["c_clip"])
            target = {"actions": actions, "vs": vs, "pg_adv": pg_adv,
                      "value_coef": loss["value_coef"],
                      "entropy_coef": loss["entropy_coef"]}
            last_mla = variables["params"][f"layer_{last}"]["mla"]
            out["rope_gradient"] = jax.grad(_rope_loss)(
                rope_columns(last_mla, dims), variables, dims, last,
                inputs[last], pos, episode,
                jax.lax.stop_gradient(target), low, rope, theta, held, shared)
        return out

    blocked = lambda x, axis: _env_blocks(x, axis, n)
    extra = None if loss is None else tuple(
        blocked(fragment[k], 1) for k in ("behaviour_logp", "rewards", "done"))
    out = jax.lax.map(block, (
        blocked(fragment["history_obs"], 1), blocked(fragment["history_done"], 1),
        blocked(fragment["bootstrap_obs"], 0), blocked(fragment["actions"], 1),
        None if carries is None else jax.tree.map(lambda c: blocked(c, 0), carries),
        extra,
    ))
    join = lambda x: jnp.moveaxis(x, 0, 1).reshape(T, B, *x.shape[3:])
    flat = lambda x: x.reshape(B, *x.shape[2:])
    view = {k: join(out[k]) for k in ("logp", "entropy_of", "values", "hidden")}
    view.update(
        bootstrap_value=out["bootstrap_value"].reshape(B),
        mla_rows_attended=jnp.sum(out["attended_sum"]) / (T * B),
        mla_rows_cached=jnp.sum(out["cached_sum"]) / B,
    )
    if carries is None:
        view["core_before"] = jax.tree.map(flat, out["core_before"])
        view["core"] = jax.tree.map(flat, out["core"])
    else:
        view["carry_gaps"] = {
            when: carry_gaps(*(jnp.sum(x, axis=0) for x in sums))
            for when, sums in out["carry_gaps"].items()}
    if loss is not None:
        view["rope_gradient"] = jax.tree.map(
            lambda g: jnp.sum(g, axis=0) / (T * B), out["rope_gradient"])
    return view


def impala_loss(variables, dims, fragment, gamma, value_coef, entropy_coef,
                rho_clip=1.0, c_clip=1.0, env_block=1, **how):
    """The IMPALA loss of one fragment and the rest of the reference's view
    of the update that trains on it (``evaluate``'s with the gradient of the
    last layer's rope columns, the loss's terms, and what ``loss_of`` and
    ``tail_gradient`` read). ``variables`` in the published order."""
    loss = {"gamma": gamma, "value_coef": value_coef, "entropy_coef": entropy_coef,
            "rho_clip": rho_clip, "c_clip": c_clip}
    view = evaluate(variables, dims, fragment, env_block, loss=loss, **how)
    view.update(_loss_terms(fragment, view, gamma, rho_clip, c_clip))
    total = (view["pg_loss"] + value_coef * view["value_loss"]
             - entropy_coef * view["entropy"])
    return total, view


def forward(variables, dims, tokens, done, low=False, **how):
    """``tokens``, ``done`` [N, b], from caches empty before the first:
    (logits [N, b, V], values [N, b]) (tests; the last token stands in the
    bootstrap token's place). ``variables`` in the published order."""
    h, _, _, _ = trunk(variables, dims, tokens, done, tokens.shape[0] - 1, low, **how)
    return heads(variables["params"], dims, h, low)
