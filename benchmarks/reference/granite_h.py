"""Plain float32 ``jax.numpy`` reference of the ``granite_h_rl`` policy and its
loss: granite-4.0-h-micro (``granitemoehybrid``, no experts) as the published
config and the family's published code give its layers, one period of them
and an eighth of the vocabulary.

Independent of the code under test: nothing here imports ``asyncrl_tpu``; it
reads the program's parameters by name (``models/granite_h.py
GraniteHPolicy.init`` lists them) and the model's sizes from the
configuration file's ``model`` record; what it shares with the other plain
references of a token-level policy (products, norms, V-trace and the loss's
terms, positions from the ``done`` flags, a cache rebuilt from rows) it
imports from ``reference/lfm2_moe.py`` and ``reference/keye_moe.py``. Every
product runs at ``Precision.HIGHEST``.

NO CACHE: the reference is given each env's tokens and ``done`` flags since
its state was empty (the whole history, the fragment last) and one more
token, the bootstrap observation, and computes every layer over all of it.
A Mamba-2 layer by its ONE-TOKEN RECURRENCE (``lax.scan``), in the order of
the published torch path: ``[z, xBC, dt] = x W_in``; the causal depthwise
conv of 4 over ``xBC`` by its window of the episode's last four inputs, plus
its bias, then ``silu``; ``[u, B, C]``; ``delta = softplus(dt + dt_bias)``;
``S <- exp(-exp(A_log) delta) S + delta u B^T`` in float32, zero at an
episode's start; ``y = S C + D u``; ``RMSNorm(y * silu(z))`` over all 4,096
channels (one group); ``W_out``. Attention over every earlier row of the
token's episode, no positions (NoPE), no q/k norm, the softmax's scale
``attention_multiplier``; query head j reads key-value head j // 4. The
residual branches are scaled by ``residual_multiplier``, the embedding by
``embedding_multiplier``, and the logits are ``RMSNorm(h) E^T /
logits_scaling`` with ``E`` the embedding (tied). What a carry would hold
after any token is rebuilt: the state and conv window by the recurrence, the
cache's rows by ``carry_at``.

Departures from the published model, all shared with the program: layers
0-9 of 40, the held slice of the vocabulary, a value head (the RL
addition), seeded random weights.

Wrong on purpose (``how``): ``state_low=True`` (the state rounded to
bfloat16 after every token), ``dt_bias=False`` (delta without it),
``residual`` (another residual multiplier), ``conv_bias=False`` (the conv's
bias left out); ``low=True`` is the same computation in bfloat16 throughout,
the nearest precision below the one the configuration states (every
product's operands and each of ``PARTS``), or ``low`` names some of
``PARTS``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import plain
from benchmarks.reference.keye_moe import _einsum, carry_at, positions
from benchmarks.reference.lfm2_moe import (  # noqa: F401  (re-exported)
    BF16,
    F32,
    HIGHEST,
    _entropy,
    _env_blocks,
    _is_low,
    _log_softmax,
    _loss_terms,
    _rms,
    _taken,
    loss_of,
)

# What the configuration keeps in float32 and ``low`` runs in bfloat16: the
# products' results and the residual stream; the conv's output and the gate;
# the norms; the softmax of attention and the head's log-softmax; the
# state-space state and its decay.
PARTS = ("activations", "gates", "norms", "softmax", "state", "decay")
TAIL = ("final_norm", "value")  # the leaves after the last layer
SSD_LEAVES = ("A_log", "dt_bias", "D")  # a Mamba layer's: the recurrence's own


def last_mamba(dims: dict) -> int:
    return max(i for i, kind in enumerate(dims["layers"]) if kind.startswith("mamba"))


def _round(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), as float32, by
    its bits: a compiler allowed excess precision (XLA on a TPU) may leave
    out a convert to bfloat16 and back. The gradient passes straight
    through, as a convert's does: a bit cast has none."""
    x = x.astype(F32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return x + jax.lax.stop_gradient(jax.lax.bitcast_convert_type(bits, F32) - x)


def _keep(x, low, part):
    """``x``, rounded to bfloat16 where ``low`` covers ``part``."""
    return _round(x) if _is_low(low, part) else x


def _mm(x, w, low):
    if low:
        return _keep(jnp.matmul(x.astype(BF16), w.astype(BF16),
                                preferred_element_type=F32), low, "activations")
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _swiglu(p, x, low):
    return _mm(jax.nn.silu(_mm(x, p["gate"], low)) * _mm(x, p["up"], low), p["down"], low)


def mamba_layer(p, x, done, dims, carry, cuts=(), low=False, state_low=False,
                dt_bias=True, conv_bias=True):
    """``x`` [N, b, D], ``done`` [N, b]; ``carry`` the state and conv window
    before the first token (``{"S" [b, H, P, Ns], "conv" [b, 3, xBC]}``).
    Returns (y [N, b, D], the carry before token n for each n of ``cuts``:
    after token n-1, zero where it ended its episode)."""
    H, P, Ns = dims["mamba_heads"], dims["mamba_head_dim"], dims["mamba_state"]
    inner = H * P
    N, b, _ = x.shape
    proj = _mm(x, p["in"], low)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * Ns], axis=-1)
    A = -jnp.exp(p["A_log"])
    bias = p["conv_bias"] if conv_bias else 0.0
    shift = p["dt_bias"] if dt_bias else 0.0

    def token(carry, inputs):
        S, window = carry
        xbc_t, dt_t, done_t = inputs
        window = jnp.concatenate([window, xbc_t[:, None]], axis=1)  # [b, 4, xBC]
        conv = _keep(jax.nn.silu(jnp.sum(window * p["conv"][None], axis=1) + bias),
                     low, "gates")
        u = conv[:, :inner].reshape(b, H, P)
        B, C = conv[:, inner:inner + Ns], conv[:, inner + Ns:]
        delta = jax.nn.softplus(dt_t + shift)
        a = _keep(jnp.exp(A * delta), low, "decay")
        S = a[..., None, None] * S + (delta[..., None] * u)[..., None] * B[:, None, None, :]
        if state_low or _is_low(low, "state"):
            S = _round(S)
        y = jnp.sum(S * C[:, None, None, :], axis=-1) + p["D"][:, None] * u
        alive = 1.0 - done_t.astype(F32)
        return (S * alive[:, None, None, None], window[:, 1:] * alive[:, None, None]), y

    carries, ys, state = [], [], (carry["S"].astype(F32), carry["conv"].astype(F32))
    for lo, hi in zip((0, *cuts), (*cuts, N)):
        state, y = jax.lax.scan(token, state, (xbc[lo:hi], dt[lo:hi], done[lo:hi]))
        carries.append({"S": state[0], "conv": state[1]})
        ys.append(y)
    y = jnp.concatenate(ys, axis=0).reshape(N, b, inner)
    y = _rms(_keep(y * jax.nn.silu(z), low, "gates"), p["norm"], dims["eps"], low)
    return _mm(y, p["out"], low), carries[:-1]


def empty_carry(dims: dict, b: int) -> dict:
    xbc = dims["mamba_heads"] * dims["mamba_head_dim"] + 2 * dims["mamba_state"]
    return {"S": jnp.zeros((b, dims["mamba_heads"], dims["mamba_head_dim"],
                            dims["mamba_state"]), F32),
            "conv": jnp.zeros((b, dims["conv_width"] - 1, xbc), F32)}


def attention_layer(p, x, episode, dims, n_last, low=False):
    """``x`` [N, b, D], every token of the history and the bootstrap token
    last (N = whole blocks of ``n_last`` queries + 1), queries in blocks of
    ``n_last``. Returns (y [N, b, D], the key and value rows [N, b, Hkv *
    dh])."""
    H, G, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    N, b, _ = x.shape
    q = _mm(x, p["q"], low).reshape(N, b, H, dh)
    k, v = (_mm(x, p[n], low).reshape(N, b, G, dh) for n in ("k", "v"))
    keys, values = (jnp.repeat(a, H // G, axis=2) for a in (k, v))
    time = jnp.arange(N)

    def block(args):
        q, episode_q, time_q = args
        valid = (episode.T[:, None, :] == episode_q.T[:, :, None]) & (
            time[None, None, :] <= time_q[None, :, None])  # [b, Q, N]
        scores = _einsum("qbhd,sbhd->bhqs", q, keys, low) * dims["attention_multiplier"]
        scores = jnp.where(valid[:, None], scores, -jnp.inf)
        if _is_low(low, "softmax"):
            scores = scores.astype(BF16)
        probs = jax.nn.softmax(scores, axis=-1).astype(F32)
        return _einsum("bhqs,sbhd->qbhd", probs, values, low)

    n = (N - 1) // n_last
    blocks = lambda a: a[:N - 1].reshape(n, n_last, *a.shape[1:])
    out = jax.lax.map(block, tuple(blocks(a) for a in (q, episode, time)))
    out_boot = block(tuple(a[N - 1:] for a in (q, episode, time)))
    out = jnp.concatenate([out.reshape(N - 1, *out.shape[2:]), out_boot], axis=0)
    y = _mm(out.reshape(N, b, H * dh), p["o"], low)
    return y, {"k": k.reshape(N, b, G * dh), "v": v.reshape(N, b, G * dh)}


def trunk(variables, dims, tokens, done, n_last, cuts=(), low=False, state_low=False,
          dt_bias=True, conv_bias=True, residual=None):
    """``tokens``, ``done`` [N, b]: the history and the bootstrap token.
    Returns (the last layer's output [N, b, D], by layer the carry before
    each token of ``cuts``, by layer its input and normed input)."""
    params = variables["params"]
    pos, episode = positions(done)
    r = dims["residual_multiplier"] if residual is None else residual
    h = _keep(params["embed"].astype(F32)[tokens] * dims["embedding_multiplier"],
              low, "activations")
    carries, inputs = [], []
    for i, kind in enumerate(dims["layers"]):
        p = params[f"layer_{i}"]
        x = _rms(h, p["norm_mixer"], dims["eps"], low)
        inputs.append((h, x))
        if kind.startswith("mamba"):
            y, at = mamba_layer(p["mamba"], x, done, dims, empty_carry(dims, x.shape[1]),
                                cuts, low, state_low, dt_bias, conv_bias)
        else:
            y, rows = attention_layer(p["gqa"], x, episode, dims, n_last, low)
            at = [carry_at(rows, pos, n, dims["max_positions"]) for n in cuts]
        h = _keep(h + r * y, low, "activations")
        carries.append(at)
        x = _rms(h, p["norm_ffn"], dims["eps"], low).reshape(-1, h.shape[-1])
        h = _keep(h + r * _swiglu(p["ffn"], x, low).reshape(h.shape), low, "activations")
    return h, carries, inputs


def heads(params, dims, h, low=False):
    """The last layer's output -> (logits [..., V], values [...]): the head
    is the embedding (tied), the logits divided by ``logits_scaling``."""
    h = _rms(h, params["final_norm"], dims["eps"], low)
    if low:
        logits = jnp.einsum("...d,vd->...v", h.astype(BF16),
                            params["embed"].astype(BF16), preferred_element_type=F32)
    else:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"].astype(F32),
                            precision=HIGHEST)
    values = _mm(h, params["value"]["kernel"], low)[..., 0] + params["value"]["bias"][0]
    return _keep(logits, low, "activations") / dims["logits_scaling"], values


def _block_loss(params, dims, h, fragment, low):
    """The summed IMPALA loss of a block's fragment from its last layer's
    output ``h`` [T, b, D], the V-trace targets constants."""
    logits, values = heads(params, dims, h, low)
    logp_all = _log_softmax(logits, low)
    return (-jnp.sum(_taken(logp_all, fragment["actions"]) * fragment["pg_adv"])
            + fragment["value_coef"] * 0.5 * jnp.sum(jnp.square(fragment["vs"] - values))
            - fragment["entropy_coef"] * jnp.sum(_entropy(logp_all)))


def _ssd_loss(leaves, variables, dims, last, inputs, carry, done, fragment, low,
              state_low, dt_bias, conv_bias, residual):
    """The block's share of the loss, summed over its fragment's tokens, as
    a function of the last Mamba layer's ``A_log``, ``dt_bias`` and ``D``:
    that layer again over the fragment from the state and window before it
    (data, as the program's carry is), then the layers after it and the
    heads; the V-trace targets constants."""
    params = variables["params"]
    layer = params[f"layer_{last}"]
    r = dims["residual_multiplier"] if residual is None else residual
    h, x = inputs
    y, _ = mamba_layer({**layer["mamba"], **leaves}, x, done, dims,
                       jax.lax.stop_gradient(carry), (), low, state_low, dt_bias, conv_bias)
    h = h + r * y
    h = h + r * _swiglu(layer["ffn"], _rms(h, layer["norm_ffn"], dims["eps"], low)
                        .reshape(-1, h.shape[-1]), low).reshape(h.shape)
    return _block_loss(params, dims, h, fragment, low)


def carry_gap(mine: list, theirs: list, dims: dict) -> dict:
    """One carry held to another, by layer: ``|mine - theirs| / |theirs|``
    of a Mamba layer's state and conv window ([Mamba layers] each), of an
    attention layer's key and value rows up to ``theirs``' ``len`` (the
    larger of the two; [attention layers]), and the envs whose ``len``
    differs."""
    def gap(a, b, live=None):
        a, b = a.astype(F32), b.astype(F32)
        if live is not None:
            a, b = jnp.where(live, a, 0.0), jnp.where(live, b, 0.0)
        return jnp.sqrt(jnp.sum(jnp.square(a - b)) / jnp.maximum(jnp.sum(b * b), 1e-30))

    S, conv, rows, lens = [], [], [], []
    for a, b, kind in zip(mine, theirs, dims["layers"]):
        if kind.startswith("mamba"):
            S.append(gap(a["S"], b["S"]))
            conv.append(gap(a["conv"], b["conv"]))
            continue
        live = (jnp.arange(b["k"].shape[1])[None, :] < b["len"][:, None])[..., None]
        rows.append(jnp.maximum(gap(a["k"], b["k"], live), gap(a["v"], b["v"], live)))
        lens.append(jnp.sum(a["len"] != b["len"]))
    return {"S": jnp.stack(S), "conv": jnp.stack(conv), "rows": jnp.stack(rows),
            "len": jnp.stack(lens)}


def chunk_boundaries(done, chunk: int):
    """Mean episode boundaries a chunk of ``chunk`` tokens masks inside
    itself over a fragment ``done`` [T, B]: a done token that is not its
    chunk's last or the fragment's."""
    T, B = done.shape
    n = min(chunk, T)
    t = jnp.arange(T)[:, None]
    inside = (t % n != n - 1) & (t != T - 1)
    return jnp.sum(done & inside) / (B * -(-T // n))


def evaluate(variables, dims, fragment, env_block: int, loss=None, carry_dtype=None,
             low=False, **how):
    """One fragment seen through its history, in blocks of ``env_block``
    envs. ``fragment``: ``history_obs``, ``history_done`` [Th, B] (the
    fragment's T steps last), ``bootstrap_obs`` [B], ``actions`` [T, B]; with
    ``loss`` (``gamma``, ``value_coef``, ``entropy_coef``, ``rho_clip``,
    ``c_clip``) also ``behaviour_logp``, ``rewards``, ``done`` [T, B].
    Returns a dict: ``logp``, ``entropy_of``, ``values``, ``hidden`` [T, B,
    ...], ``bootstrap_value`` [B], ``gqa_rows_attended`` (mean rows a query
    of the fragment attended), ``ssd_chunk_resets``, ``core_before`` and
    ``core`` (by layer, the carry before and after the fragment, the rows
    cast to ``carry_dtype`` if given); with ``loss`` ``ssd_gradient``, d
    loss / d (the last Mamba layer's ``SSD_LEAVES``), a mean over tokens."""
    T, B = fragment["actions"].shape
    Th = fragment["history_obs"].shape[0]
    n = B // env_block
    last = last_mamba(dims)
    if last != len(dims["layers"]) - 1:
        raise ValueError("the gradient of the last Mamba layer's own leaves "
                         "is taken where it is the model's last layer")
    params = variables["params"]

    def block(args):
        obs, done, boot, actions, extra = args  # env axis leading
        obs, done, actions = (jnp.moveaxis(a, 0, 1) for a in (obs, done, actions))
        tokens = jnp.concatenate([obs, boot[None]], axis=0)
        done = jnp.concatenate([done, jnp.zeros_like(done[:1])], axis=0)
        h, carries, inputs = trunk(variables, dims, tokens, done, T, (Th - T, Th), low, **how)
        pos, _ = positions(done)
        logits, values = heads(params, dims, h[Th - T:], low)
        logp_all = _log_softmax(logits[:T], low)
        out = {
            "logp": _taken(logp_all, actions), "entropy_of": _entropy(logp_all),
            "values": values[:T], "bootstrap_value": values[T], "hidden": h[Th - T:Th],
            "attended_sum": jnp.sum(pos[Th - T:Th] + 1),
            "core_before": [c[0] for c in carries], "core": [c[1] for c in carries],
        }
        if loss is not None:
            behaviour, rewards, frag_done = (jnp.moveaxis(a, 0, 1) for a in extra)
            vs, pg_adv = plain.vtrace_sequential(
                behaviour.astype(F32), out["logp"], rewards.astype(F32),
                loss["gamma"] * (1.0 - frag_done.astype(F32)), out["values"],
                out["bootstrap_value"], loss["rho_clip"], loss["c_clip"])
            target = jax.lax.stop_gradient({
                "actions": actions, "vs": vs, "pg_adv": pg_adv,
                "value_coef": loss["value_coef"], "entropy_coef": loss["entropy_coef"]})
            h_in, x = inputs[last]
            mamba = params[f"layer_{last}"]["mamba"]
            out["ssd_gradient"] = jax.grad(_ssd_loss)(
                {k: mamba[k] for k in SSD_LEAVES}, variables, dims, last,
                (h_in[Th - T:Th], x[Th - T:Th]), carries[last][0], done[Th - T:Th],
                target, low, how.get("state_low", False), how.get("dt_bias", True),
                how.get("conv_bias", True), how.get("residual"))
        return out

    blocked = lambda x, axis: _env_blocks(x, axis, n)
    extra = None if loss is None else tuple(
        blocked(fragment[k], 1) for k in ("behaviour_logp", "rewards", "done"))
    out = jax.lax.map(block, (
        blocked(fragment["history_obs"], 1), blocked(fragment["history_done"], 1),
        blocked(fragment["bootstrap_obs"], 0), blocked(fragment["actions"], 1), extra,
    ))
    join = lambda x: jnp.moveaxis(x, 0, 1).reshape(T, B, *x.shape[3:])
    flat = lambda x: x.reshape(B, *x.shape[2:])

    def carry_of(layers):
        cast = lambda c: {k: v.astype(carry_dtype) if k in ("k", "v") and carry_dtype else v
                          for k, v in c.items()}
        return [cast(jax.tree.map(flat, c)) for c in layers]

    view = {k: join(out[k]) for k in ("logp", "entropy_of", "values", "hidden")}
    view.update(
        bootstrap_value=out["bootstrap_value"].reshape(B),
        gqa_rows_attended=jnp.sum(out["attended_sum"]) / (T * B),
        ssd_chunk_resets=chunk_boundaries(fragment["history_done"][Th - T:], dims["chunk"]),
        core_before=carry_of(out["core_before"]), core=carry_of(out["core"]),
    )
    if loss is not None:
        view["ssd_gradient"] = jax.tree.map(
            lambda g: jnp.sum(g, axis=0) / (T * B), out["ssd_gradient"])
    return view


def impala_loss(variables, dims, fragment, gamma, value_coef, entropy_coef,
                rho_clip=1.0, c_clip=1.0, env_block=1, **how):
    """The IMPALA loss of one fragment and the rest of the reference's view
    of the update that trains on it (``evaluate``'s with the gradient of the
    last Mamba layer's own leaves, the loss's terms, and what ``loss_of``
    and ``tail_gradient`` read)."""
    loss = {"gamma": gamma, "value_coef": value_coef, "entropy_coef": entropy_coef,
            "rho_clip": rho_clip, "c_clip": c_clip}
    view = evaluate(variables, dims, fragment, env_block, loss=loss, **how)
    view.update(_loss_terms(fragment, view, gamma, rho_clip, c_clip))
    total = (view["pg_loss"] + value_coef * view["value_loss"]
             - entropy_coef * view["entropy"])
    return total, view


def tail_gradient(variables, dims, fragment, view, value_coef, entropy_coef,
                  env_block=1, low=False, **_):
    """d loss / d (``TAIL`` leaves), from ``view`` (``impala_loss``'s second
    result): the leaves after the last layer, whose gradient needs no
    backward pass through the layers. In blocks of envs."""
    T, B = fragment["actions"].shape
    n = B // env_block
    params = variables["params"]
    tail = {k: params[k] for k in TAIL}

    def block_loss(tail, args):
        h, actions, pg_adv, vs = args
        target = {"actions": actions, "pg_adv": pg_adv, "vs": vs,
                  "value_coef": value_coef, "entropy_coef": entropy_coef}
        return _block_loss({**params, **tail}, dims, h, target, low) / (T * B)

    def add_block(total, args):
        return jax.tree.map(jnp.add, total, jax.grad(block_loss)(tail, args)), None

    total, _ = jax.lax.scan(
        add_block, jax.tree.map(jnp.zeros_like, tail),
        tuple(_env_blocks(x, 1, n) for x in (
            view["hidden"], fragment["actions"], view["pg_adv"], view["vs"])),
    )
    return total


def forward(variables, dims, tokens, done, low=False, **how):
    """``tokens``, ``done`` [N, b], from an empty state before the first:
    (logits [N, b, V], values [N, b]) (tests; the last token stands in the
    bootstrap token's place)."""
    h, _, _ = trunk(variables, dims, tokens, done, tokens.shape[0] - 1, (), low, **how)
    return heads(variables["params"], dims, h, low)

