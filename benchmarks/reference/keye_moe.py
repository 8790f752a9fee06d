"""Plain float32 ``jax.numpy`` reference of the ``keye_moe_rl`` policy and its
loss: the language model of Keye-VL-2.0-30B-A3B as the published config, the
Qwen3-MoE family's code and DeepSeek's published indexer give its layers, one
chip's share of the experts and of the vocabulary.

Independent of the code under test: nothing here imports ``asyncrl_tpu``; it
reads the program's parameters by name (``models/keye_moe.py KeyePolicy.init``
lists them) and the model's sizes from the configuration file's ``model``
record; what it shares with the other plain reference of a token-level
policy (products, norms, rotation, the heads, V-trace and the loss's terms)
it imports from ``reference/lfm2_moe.py``. Every product runs at
``Precision.HIGHEST``.

NO CACHE: the reference is given each env's tokens and ``done`` flags since
its caches were empty (the whole history, the fragment last) and one more
token, the bootstrap observation. It computes every layer over all of it:
a token's position is its index in its episode, counted from the flags;
every earlier row of a token's episode is scored by the indexer; the top-k
is exact (the k-th largest by ``lax.top_k``, ties admitted in row order);
the heads' softmax runs over the chosen rows; the experts by a loop over the
held ids. What a cache would hold after any token is rebuilt from the rows
(``carry_at``), to hold the program's cache against.

The indexer's loss, added to the IMPALA loss with coefficient 1: ``L_I =
sum over layers of mean over the fragment's queries of KL(P_t || softmax
over S_t of I[t, .])``, ``P_t`` the heads' probabilities averaged over the
heads. ``indexer_gradient`` is d ``L_I`` / d (the last layer's indexer
leaves), the only term that reaches them, with the rows before the fragment
held constant (the program's cached rows are data).

Wrong on purpose (``how``): ``topk`` (another number of rows), ``relu=False``,
``drop_index_head=j`` (one indexer head's weight taken as 0),
``router="sigmoid"``, ``held`` (another set of experts' parts added);
``low=True`` is the same computation in bfloat16 throughout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

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


def _layer_norm(x, scale, bias, eps, low=False):
    if _is_low(low, "norms"):
        x, scale, bias = (a.astype(BF16) for a in (x, scale, bias))
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * scale + bias).astype(F32)


def _einsum(spec, a, b, low):
    if low:
        return jnp.einsum(spec, a.astype(BF16), b.astype(BF16),
                          preferred_element_type=F32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def exact_top_k(scores, valid, k: int):
    """The ``k`` largest of ``scores`` [..., P] among ``valid`` (all of them
    where there are no more), ties in row order: (mask, the k-th largest
    score, -inf where every valid row is chosen)."""
    masked = jnp.where(valid, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, min(k, scores.shape[-1]))[0][..., -1:]
    if k > scores.shape[-1]:
        kth = jnp.full_like(kth, -jnp.inf)
    above = masked > kth
    ties = valid & (masked == kth)
    need = jnp.minimum(k, jnp.sum(valid, axis=-1, keepdims=True)) - jnp.sum(
        above, axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= need)), kth[..., 0]


def positions(done):
    """``done`` [N, b] -> (a token's index in its episode, its episode's
    number) [N, b], from caches empty before the first token."""
    def index(carry, done_t):
        return jnp.where(done_t, 0, carry + 1), carry

    _, pos = jax.lax.scan(index, jnp.zeros(done.shape[1:], jnp.int32), done)
    ends = jnp.cumsum(done.astype(jnp.int32), axis=0)
    return pos, ends - done.astype(jnp.int32)


def index_projections(p, x, pos, dims, low=False, drop_index_head=None):
    """The indexer's queries [N, b, J, dI], its key rows [N, b, dI] and its
    heads' weights [N, b, J], of the layer's normed input ``x`` [N, b, D]."""
    J, dI = dims["index_heads"], dims["index_dim"]
    x = jax.lax.stop_gradient(x)  # the indexer trains on its own loss only
    qi = _rope(_mm(x, p["q"], low).reshape(*x.shape[:-1], J, dI), pos,
               dims["rope_theta"], low)
    ki = _layer_norm(_mm(x, p["k"], low), p["k_norm"], p["k_bias"], dims["eps"], low)
    ki = _rope(ki[..., None, :], pos, dims["rope_theta"], low)[..., 0, :]
    w = _mm(x, p["w"], low)
    if drop_index_head is not None:
        w = w.at[..., drop_index_head].set(0.0)
    return qi, ki, w


def index_scores(qi, w, ki, dims, low=False, relu=True):
    """``qi`` [Q, b, J, dI], ``w`` [Q, b, J], ``ki`` [N, b, dI] -> [b, Q, N]."""
    products = _einsum("qbjd,sbd->bjqs", qi, ki, low)
    if relu:
        products = jax.nn.relu(products)
    scale = dims["index_dim"] ** -0.5 * dims["index_heads"] ** -0.5
    return scale * jnp.sum(products * jnp.moveaxis(w, 0, -1)[..., None], axis=1)


def _kl(target, scores, chosen):
    """Per query: KL(target || softmax over the chosen rows of scores), the
    target a constant of the loss."""
    target = jax.lax.stop_gradient(target)
    log_pi = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    live = chosen & (target > 0)
    return jnp.sum(jnp.where(
        live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_pi), 0.0), axis=-1)


def dsa_layer(p, x, pos, episode, dims, n_last, low=False, topk=None,
              relu=True, drop_index_head=None):
    """``x`` [N, b, D], every token of the history and the bootstrap token
    last (N = whole blocks of ``n_last`` queries + 1). Returns (y [N, b, D],
    the rows {"k", "v" [N, b, G * dh], "ki" [N, b, dI]}, and of the LAST
    block of ``n_last`` queries before the bootstrap token, the fragment's:
    {"scores", "chosen", "target" [b, n_last, N], "kth", "kl", "scored"
    [b, n_last]})."""
    H, G, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    topk = dims["index_top_k"] if topk is None else topk
    N, b, _ = x.shape
    q = _mm(x, p["q"], low).reshape(N, b, H, dh)
    k = _mm(x, p["k"], low).reshape(N, b, G, dh)
    v = _mm(x, p["v"], low).reshape(N, b, G, dh)
    q = _rope(_rms(q, p["q_norm"], dims["eps"], low), pos, dims["rope_theta"], low)
    k = _rope(_rms(k, p["k_norm"], dims["eps"], low), pos, dims["rope_theta"], low)
    qi, ki, w = index_projections(p["index"], x, pos, dims, low, drop_index_head)
    time = jnp.arange(N)
    # a key-value head at a time: [G, N, b, dh], and its H / G query heads
    k_g, v_g = (jnp.moveaxis(a, 2, 0) for a in (k, v))

    def block(args):
        q, qi, w, episode_q, time_q = args  # [Q, b, ...], time_q [Q]
        scores = index_scores(qi, w, ki, dims, low, relu)  # [b, Q, N]
        valid = (episode.T[:, None, :] == episode_q.T[:, :, None]) & (
            time[None, None, :] <= time_q[None, :, None])
        chosen, kth = exact_top_k(scores, valid, topk)

        def group(args):
            q, keys, values = args  # [Q, b, H / G, dh], [N, b, dh] x 2
            attn = _einsum("qbhd,sbd->bhqs", q, keys, low) / math.sqrt(dh)
            attn = jnp.where(chosen[:, None], attn, -jnp.inf)
            if _is_low(low, "softmax"):
                attn = attn.astype(BF16)
            probs = jax.nn.softmax(attn, axis=-1).astype(F32)
            return _einsum("bhqs,sbd->qbhd", probs, values, low), jnp.sum(probs, axis=1)

        out, summed = jax.lax.map(group, (
            jnp.moveaxis(q.reshape(*q.shape[:2], G, H // G, dh), 2, 0), k_g, v_g))
        out = jnp.moveaxis(out, 0, 2).reshape(*q.shape)
        target = jnp.sum(summed, axis=0) / H
        seen = {"scores": scores, "chosen": chosen, "target": target, "kth": kth,
                "kl": _kl(target, scores, chosen), "scored": jnp.sum(valid, axis=-1)}
        return out, seen

    n = (N - 1) // n_last
    blocks = lambda a: a[:N - 1].reshape(n, n_last, *a.shape[1:])
    queries = (q, qi, w, episode, time)
    # all but the fragment's block: only the heads' outputs are kept
    out, _ = jax.lax.map(
        lambda a: (block(a)[0], None), tuple(blocks(a)[:-1] for a in queries))
    out_last, seen = block(tuple(blocks(a)[-1] for a in queries))
    out_boot, _ = block(tuple(a[N - 1:] for a in queries))
    out = jnp.concatenate([out.reshape(-1, b, H, dh), out_last, out_boot], axis=0)
    y = _mm(out.reshape(N, b, H * dh), p["o"], low)
    rows = {"k": k.reshape(N, b, G * dh), "v": v.reshape(N, b, G * dh), "ki": ki}
    return y, rows, seen


def expert_layer(p, x, dims, low=False, held=None, router="softmax"):
    """``x`` [N, D] -> the held experts' weighted part: a softmax over all
    the experts in float32, the top k, weights renormalised over the chosen
    (``norm_topk_prob``), no bias, no shared expert."""
    ids_held = list(dims["held_experts"])
    held = ids_held if held is None else list(held)
    logits = _mm(x, p["router"], low)
    if _is_low(low, "router"):
        logits = logits.astype(BF16)
    scores = (jax.nn.softmax(logits, axis=-1) if router == "softmax"
              else jax.nn.sigmoid(logits)).astype(F32)
    rank = jnp.argsort(jnp.argsort(-scores, axis=-1, stable=True), axis=-1)
    chosen = rank < dims["top_k"]
    total = jnp.sum(jnp.where(chosen, scores, 0.0), axis=-1, keepdims=True)
    weights = dims["routed_scale"] * jnp.where(chosen, scores, 0.0) / total
    y = jnp.zeros_like(x, dtype=F32)
    for row, expert in enumerate(ids_held):
        if expert not in held:
            continue
        e = {k: p["experts"][k][row] for k in ("gate", "up", "down")}
        y = y + weights[:, expert:expert + 1] * _swiglu(e, x, low)
    return y


def trunk(variables, dims, tokens, done, n_last, low=False, held=None,
          router="softmax", **how):
    """``tokens``, ``done`` [N, b]: the history and the bootstrap token.
    Returns (the last layer's output [N, b, D], by layer the rows, what
    ``dsa_layer`` saw of the fragment's queries, and the layer's normed
    input)."""
    params = variables["params"]
    pos, episode = positions(done)
    h = _keep(params["embed"].astype(F32)[tokens], low, "activations")
    rows, seen, inputs = [], [], []
    for i, _ in enumerate(dims["layers"]):
        p = params[f"layer_{i}"]
        x = _rms(h, p["norm_mixer"], dims["eps"], low)
        y, r, s = dsa_layer(p["dsa"], x, pos, episode, dims, n_last, low, **how)
        h = _keep(h + y, low, "activations")
        rows.append(r)
        seen.append(s)
        inputs.append(x)
        x = _rms(h, p["norm_ffn"], dims["eps"], low).reshape(-1, h.shape[-1])
        y = expert_layer(p["ffn"], x, dims, low, held, router)
        h = _keep(h + y.reshape(h.shape), low, "activations")
    return h, rows, seen, inputs


def carry_at(rows: dict, pos, n: int, capacity: int) -> dict:
    """The cache a layer would hold before token ``n``: the rows of the
    episode in progress from position 0 ([b, capacity, .], zeros past
    ``len``) and ``len``. ``rows``: {name: [N, b, .]}; ``pos`` [N, b]."""
    length = pos[n]
    src = jnp.clip(n - length[:, None] + jnp.arange(capacity)[None, :],
                   0, pos.shape[0] - 1)
    live = (jnp.arange(capacity)[None, :] < length[:, None])[..., None]
    out = {
        name: jnp.where(live, jnp.take_along_axis(
            jnp.moveaxis(a, 0, 1), src[..., None], axis=1), 0.0)
        for name, a in rows.items()
    }
    return {**out, "len": length}


def _index_loss(index, x, pos, episode, target, chosen, dims, n_last, low, relu,
                drop_index_head):
    """Sum over the fragment's queries of the KL term, as a function of one
    layer's indexer leaves; the rows before the fragment are constants."""
    N = x.shape[0]
    first = N - 1 - n_last
    qi, ki, w = index_projections(index, x, pos, dims, low, drop_index_head)
    ki = jnp.concatenate([jax.lax.stop_gradient(ki[:first]), ki[first:]], axis=0)
    scores = index_scores(qi[first:N - 1], w[first:N - 1], ki, dims, low, relu)
    return jnp.sum(_kl(target, scores, chosen))


def history_rows(program_chosen, length0, N: int, n_last: int):
    """The program's selection [b, T, L + T], in its own coordinates (the
    cache's L rows, then the fragment's T), as rows of the history [b, T, N].
    ``length0`` [b]: the rows its cache held before the fragment; they are
    the ``length0`` tokens before the fragment's first."""
    b, T, _ = program_chosen.shape
    L = program_chosen.shape[-1] - T
    first = N - 1 - n_last  # the fragment's first token
    time = jnp.arange(N)[None, :]
    row = jnp.where(time >= first, L + time - first, time - (first - length0[:, None]))
    held = ((time >= first) | ((row >= 0) & (row < length0[:, None]))) & (time < N - 1)
    row = jnp.broadcast_to(jnp.clip(row, 0, L + T - 1)[:, None, :], (b, T, N))
    return jnp.take_along_axis(program_chosen, row, axis=-1) & held[:, None, :]


def selection_gap(seen: dict, theirs):
    """Another selection held to the reference's scores. ``seen``: a layer's
    ``dsa_layer`` record of the fragment's queries ([b, T, N]); ``theirs``
    [b, T, N] the rows the other chose. Of the rows chosen by one and not by
    the other: the largest distance of a row's reference score from the
    reference's k-th largest, in units of the chosen scores' spread (their
    standard deviation a query); the number a query of rows the other chose
    and the reference did not (largest, summed); and the queries whose two
    sets differ in size."""
    extra = theirs & ~seen["chosen"]
    missed = seen["chosen"] & ~theirs
    spread = jnp.nanstd(jnp.where(seen["chosen"], seen["scores"], jnp.nan), axis=-1)
    unit = jnp.maximum(spread, 1e-30)[..., None]
    off = jnp.where(extra | missed, jnp.abs(seen["scores"] - seen["kth"][..., None]), 0.0)
    count = jnp.sum(extra, axis=-1)
    return {
        "gap": jnp.max(off / unit),
        "extra_max": jnp.max(count), "extra_sum": jnp.sum(count),
        "size_differs": jnp.sum(
            jnp.sum(theirs, axis=-1) != jnp.sum(seen["chosen"], axis=-1)),
    }


ROWS = ("k", "v", "ki")  # the kinds of row a layer's cache holds


def carry_gap(mine: list, theirs: list):
    """One carry held to another, by layer: (sums of squares [layers, kinds]
    of ``mine - theirs`` and of ``theirs`` over the rows up to ``theirs``'
    ``len``; envs whose ``len`` differs [layers])."""
    sq, ref, lens = [], [], []
    for a, b in zip(mine, theirs):
        live = (jnp.arange(b["k"].shape[1])[None, :] < b["len"][:, None])[..., None]
        rows = [tuple(jnp.where(live, x[n].astype(F32), 0.0) for x in (a, b))
                for n in ROWS]
        sq.append(jnp.stack([jnp.sum(jnp.square(x - y)) for x, y in rows]))
        ref.append(jnp.stack([jnp.sum(jnp.square(y)) for _, y in rows]))
        lens.append(jnp.sum(a["len"] != b["len"]))
    return jnp.stack(sq), jnp.stack(ref), jnp.stack(lens)


def carry_gaps(sq, ref, lens) -> dict:
    """``carry_gap``'s sums as readings, by layer: the largest ``|mine -
    theirs| / |theirs|`` over a cache's kinds of row, and the envs whose
    ``len`` differs."""
    return {"rows": jnp.max(jnp.sqrt(sq / jnp.maximum(ref, 1e-30)), axis=-1),
            "len": lens}


def evaluate(variables, dims, fragment, env_block: int, program_chosen=None,
             history_chosen=None, keep_chosen=False, carries=None,
             carry_dtype=None, low=False, **how):
    """One fragment seen through its history. ``fragment``: ``history_obs``,
    ``history_done`` [Th, B] (the fragment's T steps last), ``bootstrap_obs``
    [B], ``actions`` [T, B]. In blocks of ``env_block`` envs. Returns a dict:
    ``logp``, ``entropy_of``, ``values``, ``hidden`` [T, B, ...],
    ``bootstrap_value`` [B], ``indexer_kl`` (``L_I``), ``core_before`` and
    ``core`` (by layer, the cache before and after the fragment, ``capacity``
    rows, cast to ``carry_dtype`` if given) or, with ``carries`` (``{"before",
    "after"}``: another's caches, by layer, the program's layout), instead of
    them ``carry_gaps`` (``{"before", "after"}``: by layer the largest
    ``|theirs - rebuilt| / |rebuilt|`` over a cache's kinds of row up to
    ``len``, and the envs whose ``len`` differs; at the timed size sixteen
    envs' rebuilt caches in float32 are 4.6 GB, so they are held where they
    are rebuilt, an env block at a time), ``indexer_gradient`` (the last
    layer's, of ``L_I``), the
    selection's counters; with ``program_chosen`` (by layer [B, T, L + T], the
    program's coordinates) or ``history_chosen`` (by layer [B, T, Th + 1])
    ``selection`` by layer (``selection_gap``); with ``keep_chosen`` its own
    selection ``chosen`` by layer [B, T, Th + 1]."""
    T, B = fragment["actions"].shape
    Th = fragment["history_obs"].shape[0]
    n = B // env_block
    L = dims["max_positions"]
    topk = how.get("topk") or dims["index_top_k"]
    relu, drop = how.get("relu", True), how.get("drop_index_head")
    last = f"layer_{len(dims['layers']) - 1}"

    def block(args):
        obs, done, boot, actions, theirs, held = args  # env axis leading
        obs, done, actions = (jnp.moveaxis(a, 0, 1) for a in (obs, done, actions))
        tokens = jnp.concatenate([obs, boot[None]], axis=0)
        done = jnp.concatenate([done, jnp.zeros_like(done[:1])], axis=0)
        h, rows, seen, inputs = trunk(variables, dims, tokens, done, T, low, **how)
        pos, episode = positions(done)
        logits, values = heads(variables["params"], dims, h[Th - T:], low)
        logp_all = _log_softmax(logits[:T], low)
        index = variables["params"][last]["dsa"]["index"]
        grad = jax.grad(_index_loss)(
            index, inputs[-1], pos, episode, seen[-1]["target"], seen[-1]["chosen"],
            dims, T, low, relu, drop)
        out = {
            "logp": _taken(logp_all, actions), "entropy_of": _entropy(logp_all),
            "values": values[:T], "bootstrap_value": values[T], "hidden": h[Th - T:Th],
            "kl_sum": sum(jnp.sum(s["kl"]) for s in seen),
            "scored_sum": sum(jnp.sum(s["scored"]) for s in seen),
            "selected_sum": sum(jnp.sum(s["chosen"]) for s in seen),
            "pruned_sum": sum(jnp.sum(s["scored"] > topk) for s in seen),
            "indexer_gradient": grad,
        }
        rebuilt = {"before": [carry_at(r, pos, Th - T, L) for r in rows],
                   "after": [carry_at(r, pos, Th, L) for r in rows]}
        if held is not None:
            out["carry_gaps"] = {k: carry_gap(held[k], rebuilt[k]) for k in rebuilt}
        else:
            cast = lambda c: {n: c[n].astype(carry_dtype or F32) for n in ROWS} | {
                "len": c["len"]}
            out["core_before"] = [cast(c) for c in rebuilt["before"]]
            out["core"] = [cast(c) for c in rebuilt["after"]]
        if theirs is not None:
            if program_chosen is not None:
                theirs = [history_rows(c, pos[Th - T], Th + 1, T) for c in theirs]
            out["selection"] = [selection_gap(s, c) for s, c in zip(seen, theirs)]
        if keep_chosen:
            out["chosen"] = [s["chosen"] for s in seen]
        return out

    theirs = program_chosen if program_chosen is not None else history_chosen
    if theirs is not None:
        theirs = [_env_blocks(c, 0, n) for c in theirs]
    out = jax.lax.map(block, (
        _env_blocks(fragment["history_obs"], 1, n),
        _env_blocks(fragment["history_done"], 1, n),
        _env_blocks(fragment["bootstrap_obs"], 0, n),
        _env_blocks(fragment["actions"], 1, n), theirs,
        None if carries is None else jax.tree.map(
            lambda c: _env_blocks(c, 0, n), carries),
    ))
    join = lambda x: jnp.moveaxis(x, 0, 1).reshape(T, B, *x.shape[3:])
    flat = lambda x: x.reshape(B, *x.shape[2:])
    queries = len(dims["layers"]) * T * B
    view = {k: join(out[k]) for k in ("logp", "entropy_of", "values", "hidden")}
    view.update(
        bootstrap_value=out["bootstrap_value"].reshape(B),
        indexer_kl=jnp.sum(out["kl_sum"]) / (T * B),
        dsa_rows_scored=jnp.sum(out["scored_sum"]) / queries,
        dsa_rows_selected=jnp.sum(out["selected_sum"]) / queries,
        dsa_pruned_share=jnp.sum(out["pruned_sum"]) / queries,
        indexer_gradient=jax.tree.map(
            lambda g: jnp.sum(g, axis=0) / (T * B), out["indexer_gradient"]),
    )
    if carries is None:
        view["core_before"] = jax.tree.map(flat, out["core_before"])
        view["core"] = jax.tree.map(flat, out["core"])
    else:
        view["carry_gaps"] = {
            when: carry_gaps(*(jnp.sum(x, axis=0) for x in sums))
            for when, sums in out["carry_gaps"].items()}
    if keep_chosen:
        view["chosen"] = jax.tree.map(flat, out["chosen"])
    if theirs is not None:
        view["selection"] = [
            {"gap": jnp.max(s["gap"]), "extra_max": jnp.max(s["extra_max"]),
             "extra_mean": jnp.sum(s["extra_sum"]) / (T * B),
             "size_differs": jnp.sum(s["size_differs"])}
            for s in out["selection"]]
    return view


def impala_loss(variables, dims, fragment, gamma, value_coef, entropy_coef,
                rho_clip=1.0, c_clip=1.0, env_block=1, **how):
    """The loss of one fragment, the IMPALA loss + ``L_I``, and the rest of
    the reference's view of the update that trains on it (``evaluate``'s,
    the loss's terms, and what ``loss_of`` and ``tail_gradient`` read)."""
    view = evaluate(variables, dims, fragment, env_block, **how)
    view.update(_loss_terms(fragment, view, gamma, rho_clip, c_clip))
    loss = (view["pg_loss"] + value_coef * view["value_loss"]
            - entropy_coef * view["entropy"] + view["indexer_kl"])
    return loss, view


def forward(variables, dims, tokens, done, low=False, **how):
    """``tokens``, ``done`` [N, b], from caches empty before the first:
    (logits [N, b, V], values [N, b]) (tests; the last token stands in the
    bootstrap token's place)."""
    h, _, _, _ = trunk(variables, dims, tokens, done, tokens.shape[0] - 1, low, **how)
    return heads(variables["params"], dims, h, low)
