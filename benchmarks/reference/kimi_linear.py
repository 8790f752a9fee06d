"""Plain float32 ``jax.numpy`` reference of the ``kimi_linear_rl`` policy
and its IMPALA loss: Kimi-Linear's layers as the published config and the
family's papers give them, one chip's share of the experts and of the
vocabulary.

Independent of the code under test: nothing here imports
``asyncrl_tpu.models``, ``ops`` or ``learn``; it reads the program's
parameters by name (``models/kimi_linear.py SeqPolicy.init`` lists them) and
the model's sizes from the configuration file's ``model`` record. Every
product runs at ``Precision.HIGHEST``. KDA is computed by its recurrence,
one token at a time (``lax.scan``; never the chunked algebra), MLA by
materialised keys and values and a full masked softmax, the experts by a
loop over the held ids with dense masks, episode ends by zeroing the state
after a done token.

Departures from the published model, all shared with the program:
layers 1-5 of 27, the held experts' part of each routed layer only (the
absent experts add nothing), the held slice of the vocabulary, a value
head (``Dense(1)`` on the final normed hidden state: the RL addition), no
auxiliary balance loss and a router correction bias that is a seeded buffer,
the query/key/value projections of a KDA layer and their short convs stored
side by side in one matrix (the same numbers), seeded random weights.

``low=True`` computes what the configuration keeps in float32 (KDA state
and decays, router scores) and every product in bfloat16 instead: the
reading a comparison's limit has to refuse. ``decay_scale`` and ``held``
make a reference that is wrong (every KDA decay scaled; another set of
experts' parts added): what the limits have to refuse at any precision.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference import plain

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16


def _mm(x, w, low):
    if low:
        return jnp.matmul(x.astype(BF16), w.astype(BF16), preferred_element_type=F32)
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _swiglu(p, x, low):
    return _mm(jax.nn.silu(_mm(x, p["gate"], low)) * _mm(x, p["up"], low), p["down"], low)


def kda_layer(p, x, done, state, dims, low=False, decay_scale=1.0):
    """``x`` [T, b, D] -> (y [T, b, D], state). One token at a time."""
    H, dk = dims["kda_heads"], dims["kda_head_dim"]
    qkv = _mm(x, p["qkv"], low)
    rate = _mm(_mm(x, p["f_down"], low), p["f_up"], low) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        rate.reshape(*x.shape[:2], H, dk)
    ) * decay_scale
    beta = jax.nn.sigmoid(_mm(x, p["beta"], low))
    keep_dtype = BF16 if low else F32

    def token(carry, inputs):
        S, tail = carry
        qkv_t, g_t, beta_t, done_t = inputs
        window = jnp.concatenate([tail, qkv_t[:, None]], axis=1)  # [b, W, 3N]
        conv = jax.nn.silu(jnp.sum(window * p["conv"][None], axis=1))
        q, k, v = (t.reshape(-1, H, dk) for t in jnp.split(conv, 3, axis=-1))
        q, k = _l2(q) / math.sqrt(dk), _l2(k)
        alpha = jnp.exp(g_t.astype(keep_dtype)).astype(F32)
        S = S.astype(F32) * alpha[..., None]  # Diag(alpha) S
        # (I - beta k k^T) S + beta k v^T
        kS = jnp.einsum("bhk,bhkv->bhv", k, S, precision=HIGHEST)
        S = S + beta_t[..., None, None] * k[..., None] * (v - kS)[..., None, :]
        o = jnp.einsum("bhk,bhkv->bhv", q, S, precision=HIGHEST)
        alive = 1.0 - done_t.astype(F32)
        S = (S * alive[:, None, None, None]).astype(keep_dtype)
        tail = window[:, 1:] * alive[:, None, None]
        return (S, tail), o

    (S, tail), o = jax.lax.scan(
        token, (state["S"].astype(keep_dtype), state["conv"].astype(F32)),
        (qkv, g, beta, done),
    )
    gate = jax.nn.sigmoid(_mm(_mm(x, p["g_down"], low), p["g_up"], low))
    o = _rms(o, p["o_norm"], dims["eps"]).reshape(*x.shape[:2], H * dk)
    return _mm(o * gate, p["o"], low), {"S": S.astype(F32), "conv": tail}


def mla_layer(p, x, done, state, dims, low=False):
    """``x`` [T, b, D]. Keys and values of every cached and fragment row
    are formed; the softmax is over the rows of the token's own episode."""
    H, dn, dr = dims["mla_heads"], dims["qk_nope"], dims["qk_rope"]
    dv, lora = dims["v_head"], dims["kv_lora"]
    T, b, _ = x.shape
    L = state["kv"].shape[1]
    q = _mm(x, p["q"], low).reshape(T, b, H, dn + dr)
    kv_a = _mm(x, p["kv_a"], low)
    latent = jnp.concatenate(
        [_rms(kv_a[..., :lora], p["kv_norm"], dims["eps"]), kv_a[..., lora:]], axis=-1
    )
    rows = jnp.concatenate(
        [state["kv"].astype(F32), jnp.swapaxes(latent, 0, 1)], axis=1
    )  # [b, L + T, lora + rope]
    kv = _mm(rows[..., :lora], p["kv_b"], low).reshape(b, L + T, H, dn + dv)
    keys = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(rows[:, :, None, lora:], (b, L + T, H, dr))],
        axis=-1,
    )
    scores = jnp.einsum(
        "tbhd,bphd->bhtp", q, keys, precision=HIGHEST
    ) / math.sqrt(dn + dr)
    # episode of each row: cached rows belong to the episode in progress
    # at the fragment's start (index 0) if they are below ``len``
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0)
    episode = (ends - done.astype(jnp.int32)).T  # [b, T]
    cached_ok = jnp.arange(L)[None, :] < state["len"][:, None]  # [b, L]
    row_episode = jnp.concatenate(
        [jnp.where(cached_ok, 0, -1), episode], axis=1
    )  # [b, L + T]
    row_time = jnp.concatenate(
        [jnp.full((b, L), -1), jnp.broadcast_to(jnp.arange(T), (b, T))], axis=1
    )
    mask = (row_episode[:, None, :] == episode[:, :, None]) & (
        row_time[:, None, :] <= jnp.arange(T)[None, :, None]
    )  # [b, T, L + T]
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhtp,bphd->tbhd", probs, kv[..., dn:], precision=HIGHEST)
    y = _mm(out.reshape(T, b, H * dv), p["o"], low)

    # the cache as one token at a time would have left it
    def token(carry, inputs):
        cache, length = carry
        row, done_t = inputs
        cache = jax.vmap(lambda c, n, r: c.at[n].set(r))(cache, length, row)
        length = jnp.where(done_t, 0, length + 1)
        return (cache, length), None

    (cache, length), _ = jax.lax.scan(
        token, (state["kv"].astype(F32), state["len"]), (latent, done)
    )
    return y, {"kv": cache, "len": length}


def expert_layer(p, x, dims, low=False, held=None):
    """``x`` [N, D] -> the held experts' weighted part + the shared expert.
    ``held``: the expert ids whose part is added (default: the
    configuration's); ``p["experts"]`` rows follow the configuration's."""
    ids_held = list(dims["held_experts"])
    held = ids_held if held is None else list(held)
    if low:
        scores = jax.nn.sigmoid(
            _mm(x, p["router"], True).astype(BF16)
        ).astype(F32)
    else:
        scores = jax.nn.sigmoid(_mm(x, p["router"], False))
    biased = scores + p["router_bias"]
    # the top k by sorting: chosen[n, e] = expert e is among token n's k
    rank = jnp.argsort(jnp.argsort(-biased, axis=-1, stable=True), axis=-1)
    chosen = rank < dims["top_k"]
    total = jnp.sum(jnp.where(chosen, scores, 0.0), axis=-1, keepdims=True)
    weights = dims["routed_scale"] * jnp.where(chosen, scores, 0.0) / total
    y = _swiglu(p["shared"], x, low)
    for row, expert in enumerate(ids_held):
        if expert not in held:
            continue
        e = {k: p["experts"][k][row] for k in ("gate", "up", "down")}
        y = y + weights[:, expert:expert + 1] * _swiglu(e, x, low)
    return y


TAIL = ("final_norm", "head", "value")  # the leaves after the last layer


def trunk(variables, dims, tokens, done, core, low=False, decay_scale=1.0,
          held=None):
    """``tokens``, ``done`` [T, b]; ``core``: one dict per layer, the
    program's carry. Returns (the last layer's output [T, b, D], core)."""
    params = variables["params"]
    h = params["embed"].astype(F32)[tokens]
    states = []
    for i, kind in enumerate(dims["layers"]):
        p = params[f"layer_{i}"]
        mixer, ffn = kind.split("+")
        x = _rms(h, p["norm_mixer"], dims["eps"])
        if mixer == "kda":
            y, state = kda_layer(p["kda"], x, done, core[i], dims, low, decay_scale)
        else:
            y, state = mla_layer(p["mla"], x, done, core[i], dims, low)
        h = h + y
        states.append(state)
        x = _rms(h, p["norm_ffn"], dims["eps"]).reshape(-1, h.shape[-1])
        if ffn == "dense":
            y = _swiglu(p["ffn"], x, low)
        else:
            y = expert_layer(p["ffn"], x, dims, low, held)
        h = h + y.reshape(h.shape)
    return h, states


def heads(tail, dims, h, low=False):
    """The last layer's output -> (logits [..., V], values [...]); ``tail``
    holds the ``TAIL`` leaves."""
    h = _rms(h, tail["final_norm"], dims["eps"])
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


def _entropy(logp_all):
    return -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)


def _taken(logp_all, actions):
    return jnp.take_along_axis(
        logp_all, actions[..., None].astype(jnp.int32), axis=-1
    )[..., 0]


def evaluate(variables, dims, fragment, env_block: int, **how):
    """(target_logp, entropy, values [T, B], bootstrap_value [B], the KDA
    layers' states after the fragment [B, H, dk, dv], the last layer's output
    [T, B, D]) of one fragment from its ``init_core``, in blocks of
    ``env_block`` envs."""
    T, B = fragment["actions"].shape
    n = B // env_block
    low = how.get("low", False)

    def block(args):
        obs, boot, actions, done, core = args  # env axis leading
        obs, actions, done = (jnp.moveaxis(a, 0, 1) for a in (obs, actions, done))
        h, core = trunk(variables, dims, obs, done, core, **how)
        logits, values = heads(variables["params"], dims, h, low)
        logp_all = plain.log_softmax(logits)
        _, boot_value, _ = forward(
            variables, dims, boot[None], jnp.zeros_like(done[:1]), core, **how
        )
        states = [layer["S"] for layer in core if "S" in layer]
        return (_taken(logp_all, actions), _entropy(logp_all), values,
                boot_value[0], states, h)

    logp, entropy, values, boot, states, h = jax.lax.map(block, (
        _env_blocks(fragment["obs"], 1, n), _env_blocks(fragment["bootstrap_obs"], 0, n),
        _env_blocks(fragment["actions"], 1, n), _env_blocks(fragment["done"], 1, n),
        jax.tree.map(lambda c: _env_blocks(c, 0, n), fragment["init_core"]),
    ))
    # [n, T, b, ...] -> [T, B, ...]
    join = lambda x: jnp.moveaxis(x, 0, 1).reshape(T, B, *x.shape[3:])
    states = [x.reshape(B, *x.shape[2:]) for x in states]
    return join(logp), join(entropy), join(values), boot.reshape(B), states, join(h)


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
    actions (``"logp"``), the KDA states it ends the fragment with
    (``"kda_states"``), and what ``loss_of`` and ``tail_gradient`` read."""
    logp, entropy, values, boot, states, hidden = evaluate(
        variables, dims, fragment, env_block, **how
    )
    view = {"logp": logp, "entropy_of": entropy, "values": values,
            "bootstrap_value": boot, "kda_states": states, "hidden": hidden}
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
        logp_all = plain.log_softmax(logits)
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
