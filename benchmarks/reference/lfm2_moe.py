"""Plain float32 ``jax.numpy`` reference of the ``lfm2_moe_rl`` policy and
its IMPALA loss: LFM2-8B-A1B's layers as the published config and the
family's code give them, one chip's share of the experts and of the
vocabulary.

Independent of the code under test: nothing here imports ``asyncrl_tpu``; it
reads the program's parameters by name (``models/lfm2_moe.py Lfm2Policy.init``
lists them) and the model's sizes from the configuration file's ``model``
record. Every product runs at ``Precision.HIGHEST``. The gated short conv is
computed by its definition, one token at a time over its window
(``lax.scan``); attention over the whole episode, every cached and fragment
row a key and a value, positions counted from the episode's start, a full
masked softmax; the experts by a loop over the held ids with dense masks;
episode ends by zeroing the conv's window and the cache's length after a
done token. No cache of the program's making is trusted for more than its
rows: the fragment-initial carry is an input (the rows an episode in
progress wrote before the fragment), as the program's is.

Departures from the published model, all shared with the program: layers
1-5 of 24, the held experts' part of each routed layer only (the absent
experts add nothing), the held slice of the vocabulary, a value head
(``Dense(1)`` on the final normed hidden state: the RL addition), no
auxiliary balance loss and an expert bias that is a seeded buffer, the
conv's three input projections stored side by side in one matrix (the same
numbers), seeded random weights.

``low=True`` is the same computation in bfloat16 throughout, the nearest
precision below the one the configuration states: every product's operands,
and each of ``PARTS`` that the configuration keeps in float32. ``low`` may
also name some of ``PARTS`` (with the products' operands): what each alone
moves. Put in the program's place and held to the float32 reference
(``loops/anakin_lfm2.py``: ``stand_in``), it is the reading a comparison's
limit has to refuse. ``held``, ``theta``, ``qk_norm=False``,
``conv_gate=False`` and ``conv_in_gate=False`` make a reference that is
wrong (another set of experts' parts added; another rotary base; the
per-head norms of q and k left out; the conv's output gate ``C`` or its
input gate ``B`` left out): what the limits have to refuse at any precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference import plain

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16


# What the configuration keeps in float32 and ``low`` runs in bfloat16: the
# products' results and the residual stream; the conv's gates; the norms
# (the layers' and the per-head ones of q and k); the rotation, angles
# included (a position past 256 is no bfloat16); the softmax of attention
# and the head's log-softmax; the router's scores.
PARTS = ("activations", "gates", "norms", "rotation", "softmax", "router")


def _is_low(low, part) -> bool:
    return low is True or (bool(low) and part in low)


def _keep(x, low, part):
    """``x``, rounded to bfloat16 where ``low`` covers ``part``."""
    return x.astype(BF16).astype(F32) if _is_low(low, part) else x


def _mm(x, w, low):
    if low:
        return _keep(jnp.matmul(x.astype(BF16), w.astype(BF16),
                                preferred_element_type=F32), low, "activations")
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps, low=False):
    if _is_low(low, "norms"):
        x, scale = x.astype(BF16), scale.astype(BF16)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale
    return y.astype(F32)


def _swiglu(p, x, low):
    return _mm(jax.nn.silu(_mm(x, p["gate"], low)) * _mm(x, p["up"], low), p["down"], low)


def conv_layer(p, x, done, state, low=False, conv_gate=True, conv_in_gate=True):
    """``x`` [T, b, D] -> (y [T, b, D], state): ``C * conv3(B * x~)``, one
    token at a time over its window of the episode's last three inputs."""
    b_gate, c_gate, xt = jnp.split(_mm(x, p["in"], low), 3, axis=-1)
    u = _keep(b_gate * xt if conv_in_gate else xt, low, "gates")

    def token(tail, inputs):
        u_t, done_t = inputs
        window = jnp.concatenate([tail, u_t[:, None]], axis=1)  # [b, 3, D]
        v = jnp.sum(window * p["conv"][None], axis=1)
        alive = 1.0 - done_t.astype(F32)
        return window[:, 1:] * alive[:, None, None], v

    tail, v = jax.lax.scan(token, state["conv"].astype(F32), (u, done))
    gated = _keep(c_gate * _keep(v, low, "gates"), low, "gates") if conv_gate else v
    return _mm(gated, p["out"], low), {"conv": tail}


def _rope(x, pos, theta, low=False):
    """``x`` [..., H, d] at positions ``pos`` [...]: rotate-half pairing
    over all d dims, frequencies theta^(-2i/d)."""
    d = x.shape[-1]
    dt = BF16 if _is_low(low, "rotation") else F32
    inv = (1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))).astype(dt)
    angle = pos.astype(dt)[..., None, None] * inv
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x = x.astype(dt)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return (x * cos + rotated * sin).astype(F32)


def gqa_layer(p, x, done, state, dims, low=False, theta=None, qk_norm=True):
    """``x`` [T, b, D]. Every cached row of the episode in progress and
    every fragment row is a key and a value; the softmax is over the rows
    of the token's own episode up to itself; a token's position is its
    index in its episode."""
    H, G, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    theta = dims["rope_theta"] if theta is None else theta
    T, b, _ = x.shape
    L = state["k"].shape[1]
    # the program's cache holds a position's key-value heads side by side
    cache = {n: state[n].astype(F32).reshape(b, L, G, dh) for n in ("k", "v")}
    # the episode of each row (the one in progress at the fragment's start
    # is 0) and its index in that episode
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0)
    episode = (ends - done.astype(jnp.int32)).T  # [b, T]
    length0 = state["len"]

    def index(carry, done_t):
        return jnp.where(done_t, 0, carry + 1), carry

    _, pos = jax.lax.scan(index, length0, done)  # [T, b]
    q = _mm(x, p["q"], low).reshape(T, b, H, dh)
    k = _mm(x, p["k"], low).reshape(T, b, G, dh)
    v = _mm(x, p["v"], low).reshape(T, b, G, dh)
    if qk_norm:
        q = _rms(q, p["q_norm"], dims["eps"], low)
        k = _rms(k, p["k_norm"], dims["eps"], low)
    q, k = _rope(q, pos, theta, low), _rope(k, pos, theta, low)
    keys = jnp.concatenate([cache["k"], jnp.swapaxes(k, 0, 1)], axis=1)
    values = jnp.concatenate([cache["v"], jnp.swapaxes(v, 0, 1)], axis=1)
    # query head j reads key-value head j // (H / G)
    keys_h, values_h = (jnp.repeat(a, H // G, axis=2) for a in (keys, values))
    if low:
        scores = jnp.einsum("tbhd,bphd->bhtp", q.astype(BF16), keys_h.astype(BF16),
                            preferred_element_type=F32)
    else:
        scores = jnp.einsum("tbhd,bphd->bhtp", q, keys_h, precision=HIGHEST)
    scores = scores / math.sqrt(dh)
    cached_ok = jnp.arange(L)[None, :] < length0[:, None]  # [b, L]
    row_episode = jnp.concatenate([jnp.where(cached_ok, 0, -1), episode], axis=1)
    row_time = jnp.concatenate(
        [jnp.full((b, L), -1), jnp.broadcast_to(jnp.arange(T), (b, T))], axis=1
    )
    mask = (row_episode[:, None, :] == episode[:, :, None]) & (
        row_time[:, None, :] <= jnp.arange(T)[None, :, None]
    )  # [b, T, L + T]
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    if _is_low(low, "softmax"):
        scores = scores.astype(BF16)
    probs = jax.nn.softmax(scores, axis=-1).astype(F32)
    if low:
        out = jnp.einsum("bhtp,bphd->tbhd", probs.astype(BF16), values_h.astype(BF16),
                         preferred_element_type=F32)
    else:
        out = jnp.einsum("bhtp,bphd->tbhd", probs, values_h, precision=HIGHEST)
    y = _mm(out.reshape(T, b, H * dh), p["o"], low)

    # the cache as one token at a time would have left it
    def token(carry, inputs):
        ck, cv, length = carry
        k_t, v_t, done_t = inputs
        write = jax.vmap(lambda c, n, r: c.at[n].set(r))
        ck, cv = write(ck, length, k_t), write(cv, length, v_t)
        return (ck, cv, jnp.where(done_t, 0, length + 1)), None

    (ck, cv, length), _ = jax.lax.scan(
        token, (cache["k"], cache["v"], length0), (k, v, done),
    )
    return y, {"k": ck.reshape(b, L, G * dh), "v": cv.reshape(b, L, G * dh),
               "len": length}


def expert_layer(p, x, dims, low=False, held=None):
    """``x`` [N, D] -> the held experts' weighted part (no shared expert).
    ``held``: the expert ids whose part is added (default: the
    configuration's; ``p["experts"]`` rows follow the configuration's)."""
    ids_held = list(dims["held_experts"])
    held = ids_held if held is None else list(held)
    scores = _mm(x, p["router"], low)
    if _is_low(low, "router"):
        scores = scores.astype(BF16)
    scores = jax.nn.sigmoid(scores).astype(F32)
    biased = scores + p["router_bias"]
    # the top k by sorting: chosen[n, e] = expert e is among token n's k
    rank = jnp.argsort(jnp.argsort(-biased, axis=-1, stable=True), axis=-1)
    chosen = rank < dims["top_k"]
    total = jnp.sum(jnp.where(chosen, scores, 0.0), axis=-1, keepdims=True)
    weights = dims["routed_scale"] * jnp.where(chosen, scores, 0.0) / (total + 1e-6)
    y = jnp.zeros_like(x, dtype=F32)
    for row, expert in enumerate(ids_held):
        if expert not in held:
            continue
        e = {k: p["experts"][k][row] for k in ("gate", "up", "down")}
        y = y + weights[:, expert:expert + 1] * _swiglu(e, x, low)
    return y


TAIL = ("final_norm", "head", "value")  # the leaves after the last layer


def trunk(variables, dims, tokens, done, core, low=False, held=None,
          theta=None, qk_norm=True, conv_gate=True, conv_in_gate=True):
    """``tokens``, ``done`` [T, b]; ``core``: one dict per layer, the
    program's carry. Returns (the last layer's output [T, b, D], core)."""
    params = variables["params"]
    h = _keep(params["embed"].astype(F32)[tokens], low, "activations")
    states = []
    for i, kind in enumerate(dims["layers"]):
        p = params[f"layer_{i}"]
        mixer, ffn = kind.split("+")
        x = _rms(h, p["norm_mixer"], dims["eps"], low)
        if mixer == "conv":
            y, state = conv_layer(
                p["conv"], x, done, core[i], low, conv_gate, conv_in_gate)
        else:
            y, state = gqa_layer(p["gqa"], x, done, core[i], dims, low, theta, qk_norm)
        h = _keep(h + y, low, "activations")
        states.append(state)
        x = _rms(h, p["norm_ffn"], dims["eps"], low).reshape(-1, h.shape[-1])
        if ffn == "dense":
            y = _swiglu(p["ffn"], x, low)
        else:
            y = expert_layer(p["ffn"], x, dims, low, held)
        h = _keep(h + y.reshape(h.shape), low, "activations")
    return h, states


def heads(tail, dims, h, low=False):
    """The last layer's output -> (logits [..., V], values [...]); ``tail``
    holds the ``TAIL`` leaves."""
    h = _rms(h, tail["final_norm"], dims["eps"], low)
    logits = _mm(h, tail["head"], low)
    values = _mm(h, tail["value"]["kernel"], low)[..., 0] + tail["value"]["bias"][0]
    return logits, values


def forward(variables, dims, tokens, done, core, low=False, **how):
    """Returns (logits [T, b, V], values [T, b], core)."""
    h, states = trunk(variables, dims, tokens, done, core, low, **how)
    return (*heads(variables["params"], dims, h, low), states)


def _env_blocks(x, axis, n):
    """Block the env axis into ``n`` blocks, blocks leading."""
    x = jnp.moveaxis(x, axis, 0)
    return x.reshape(n, x.shape[0] // n, *x.shape[1:])


def _log_softmax(logits, low):
    if _is_low(low, "softmax"):
        logits = logits.astype(BF16)
    return plain.log_softmax(logits).astype(F32)


def _entropy(logp_all):
    return -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)


def _taken(logp_all, actions):
    return jnp.take_along_axis(
        logp_all, actions[..., None].astype(jnp.int32), axis=-1
    )[..., 0]


def evaluate(variables, dims, fragment, env_block: int, **how):
    """(target_logp, entropy, values [T, B], bootstrap_value [B], the carry
    after the fragment (one dict per layer, env axis leading), the last
    layer's output [T, B, D]) of one fragment from its ``init_core``, in
    blocks of ``env_block`` envs."""
    T, B = fragment["actions"].shape
    n = B // env_block
    low = how.get("low", False)

    def block(args):
        obs, boot, actions, done, core = args  # env axis leading
        obs, actions, done = (jnp.moveaxis(a, 0, 1) for a in (obs, actions, done))
        h, core = trunk(variables, dims, obs, done, core, **how)
        logits, values = heads(variables["params"], dims, h, low)
        logp_all = _log_softmax(logits, low)
        _, boot_value, _ = forward(
            variables, dims, boot[None], jnp.zeros_like(done[:1]), core, **how
        )
        return (_taken(logp_all, actions), _entropy(logp_all), values,
                boot_value[0], core, h)

    logp, entropy, values, boot, core, h = jax.lax.map(block, (
        _env_blocks(fragment["obs"], 1, n), _env_blocks(fragment["bootstrap_obs"], 0, n),
        _env_blocks(fragment["actions"], 1, n), _env_blocks(fragment["done"], 1, n),
        jax.tree.map(lambda c: _env_blocks(c, 0, n), fragment["init_core"]),
    ))
    # [n, T, b, ...] -> [T, B, ...]
    join = lambda x: jnp.moveaxis(x, 0, 1).reshape(T, B, *x.shape[3:])
    core = jax.tree.map(lambda x: x.reshape(B, *x.shape[2:]), core)
    return join(logp), join(entropy), join(values), boot.reshape(B), core, join(h)


def _loss_terms(fragment, view, gamma, rho_clip, c_clip) -> dict:
    """V-trace over an evaluated fragment and the loss's three terms."""
    discounts = gamma * (1.0 - fragment["done"].astype(F32))
    vs, pg_adv = plain.vtrace_sequential(
        fragment["behaviour_logp"].astype(F32), view["logp"],
        fragment["rewards"].astype(F32), discounts, view["values"],
        view["bootstrap_value"], rho_clip, c_clip,
    )
    # the V-trace targets are constants of the loss (Espeholt et al. 2018,
    # section 4.2): it is differentiated with them held
    vs, pg_adv = jax.lax.stop_gradient((vs, pg_adv))
    return {
        "vs": vs, "pg_adv": pg_adv,
        "pg_loss": -jnp.mean(view["logp"] * pg_adv),
        "value_loss": 0.5 * jnp.mean(jnp.square(vs - view["values"])),
        "entropy": jnp.mean(view["entropy_of"]),
    }


def loss_of(fragment, view, gamma, value_coef, entropy_coef, rho_clip=1.0,
            c_clip=1.0):
    """The IMPALA loss of a fragment already evaluated (``view``:
    ``impala_loss``'s second result), e.g. under another ``behaviour_logp``."""
    t = _loss_terms(fragment, view, gamma, rho_clip, c_clip)
    return t["pg_loss"] + value_coef * t["value_loss"] - entropy_coef * t["entropy"]


def impala_loss(variables, dims, fragment, gamma, value_coef, entropy_coef,
                rho_clip=1.0, c_clip=1.0, env_block=8, **how):
    """The IMPALA loss of one fragment (``plain.impala_loss``'s composition
    on this policy), and the rest of the reference's view of the update that
    trains on it: the loss's three terms, its log-prob of the fragment's
    actions (``"logp"``), the carry it ends the fragment with (``"core"``),
    and what ``loss_of`` and ``tail_gradient`` read."""
    logp, entropy, values, boot, core, hidden = evaluate(
        variables, dims, fragment, env_block, **how
    )
    view = {"logp": logp, "entropy_of": entropy, "values": values,
            "bootstrap_value": boot, "core": core, "hidden": hidden}
    view.update(_loss_terms(fragment, view, gamma, rho_clip, c_clip))
    loss = (view["pg_loss"] + value_coef * view["value_loss"]
            - entropy_coef * view["entropy"])
    return loss, view


def tail_gradient(variables, dims, fragment, view, value_coef, entropy_coef,
                  env_block=8, low=False, **_):
    """d loss / d (``TAIL`` leaves), from ``view`` (``impala_loss``'s second
    result). These leaves sit after the last layer, so their gradient needs
    that layer's output and no backward pass through the layers: the one
    part of the update's gradient a plain reference can afford at the
    timed size. In blocks of envs, each block's logits formed once."""
    T, B = fragment["actions"].shape
    n = B // env_block
    tail = {k: variables["params"][k] for k in TAIL}

    def block_loss(tail, args):
        h, actions, pg_adv, vs = args  # env axis leading
        logits, values = heads(tail, dims, h, low)
        logp_all = _log_softmax(logits, low)
        return (
            -jnp.sum(_taken(logp_all, actions) * pg_adv)
            + value_coef * 0.5 * jnp.sum(jnp.square(vs - values))
            - entropy_coef * jnp.sum(_entropy(logp_all))
        ) / (T * B)

    def add_block(total, args):
        return jax.tree.map(jnp.add, total, jax.grad(block_loss)(tail, args)), None

    total, _ = jax.lax.scan(
        add_block, jax.tree.map(jnp.zeros_like, tail),
        tuple(_env_blocks(x, 1, n) for x in (
            view["hidden"], fragment["actions"], view["pg_adv"], view["vs"]
        )),
    )
    return total
