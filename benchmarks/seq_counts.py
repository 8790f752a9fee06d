"""Operations and bytes a token-level sequence policy of the Kimi-Linear
family needs, computed from shapes (``configs/kimi_linear_rl.json``'s
``model`` record) and from what the traffic fixes (episode lengths, the
share of assignments that land on held experts).

As ``flops.py``: 2 x multiply-accumulates of what the mathematics requires,
a backward pass costs twice a forward, nothing recomputed is counted (the
learner's rematerialised forward is not), and an implementation's choice is
not either: MLA counts one up-projection per token and scores over the
positions the token attends, whichever way the keys are formed; KDA counts
the recurrence in the rollout and the chunk algebra in the learner, since
those are two algorithms and each is the least of its kind.
"""

from __future__ import annotations

import math


def mean_attended_positions(min_len: int, max_len: int) -> float:
    """Positions a token attends (its own included), averaged over the
    tokens of episodes whose length is log-uniform on [min_len, max_len]:
    E[l (l + 1) / 2] / E[l]."""
    span = math.log(max_len / min_len)
    mean = (max_len - min_len) / span
    mean_sq = (max_len ** 2 - min_len ** 2) / (2 * span)
    return (mean_sq + mean) / 2 / mean


def _swiglu(width_in: int, width: int) -> int:
    return 2 * 3 * width_in * width


def kda_projection_flops(d: dict) -> int:
    """Per token: q/k/v, decay and gate low-rank pairs, beta, output, and
    the short conv."""
    D, n, r = d["hidden"], d["kda_heads"] * d["kda_head_dim"], d["low_rank"]
    products = 3 * D * n + 2 * (D * r + r * n) + D * d["kda_heads"] + n * D
    return 2 * products + 2 * d["conv_width"] * 3 * n


def kda_step_flops(d: dict) -> int:
    """The recurrence, per token: decay the state, read k^T S, write the
    rank-one update, read q^T S: 7 dk dv a head."""
    return 7 * d["kda_heads"] * d["kda_head_dim"] ** 2


def kda_chunk_flops(d: dict) -> int:
    """The chunk algebra, per token, C = chunk: the two pairwise matrices
    (2 x C dk), the two products with (I + A)^-1 (C (dk + dv)), one with the
    pairwise q.k (C dv), three with the state (dk dv each); x2 a MAC."""
    C, dk = d["chunk"], d["kda_head_dim"]
    per_head = 2 * C * (3 * dk + 2 * dk) + 6 * dk * dk
    return d["kda_heads"] * per_head


def mla_flops(d: dict, attended: float) -> float:
    """Per token: query, latent and output projections, one up-projection
    of its own latent row, scores and weighted values over ``attended``
    positions."""
    D, H = d["hidden"], d["mla_heads"]
    qk = d["qk_nope"] + d["qk_rope"]
    products = (
        D * H * qk + D * (d["kv_lora"] + d["qk_rope"])
        + d["kv_lora"] * H * (d["qk_nope"] + d["v_head"]) + H * d["v_head"] * D
    )
    return 2 * products + 2 * H * attended * (qk + d["v_head"])


def ffn_flops(d: dict, kind: str, held_per_token: float) -> float:
    """Per token. ``held_per_token``: token-expert assignments that land on
    a held expert (top_k x the held share of the router's choices)."""
    D = d["hidden"]
    if kind == "dense":
        return _swiglu(D, d["dense_ffn"])
    return (
        2 * D * d["num_experts"]
        + (1 + held_per_token) * _swiglu(D, d["expert_ffn"])
    )


def forward_flops_per_token(d: dict, attended: float, held_per_token: float,
                            form: str) -> float:
    """``form``: "step" (the rollout's) or "fragment" (the learner's)."""
    kda = kda_projection_flops(d) + (
        kda_step_flops(d) if form == "step" else kda_chunk_flops(d)
    )
    total = 2 * d["hidden"] * (d["vocab"] + 1)  # head and value head
    for kind in d["layers"]:
        mixer, ffn = kind.split("+")
        total += kda if mixer == "kda" else mla_flops(d, attended)
        total += ffn_flops(d, ffn, held_per_token)
    return total


def train_flops_per_update(d: dict, tokens: int, attended: float,
                           held_per_token: float) -> float:
    """Rollout forward (x1) + learner forward and backward (x3)."""
    return tokens * (
        forward_flops_per_token(d, attended, held_per_token, "step")
        + 3 * forward_flops_per_token(d, attended, held_per_token, "fragment")
    )


def parameters(d: dict) -> dict:
    """Parameter counts by part, as ``SeqPolicy.init`` builds them."""
    D, n, r = d["hidden"], d["kda_heads"] * d["kda_head_dim"], d["low_rank"]
    H, held = d["mla_heads"], len(d["held_experts"])
    kda = (3 * D * n + d["conv_width"] * 3 * n + 2 * (D * r + r * n) + n
           + d["kda_heads"] + D * d["kda_heads"] + d["kda_head_dim"] + n * D)
    mla = (D * H * (d["qk_nope"] + d["qk_rope"]) + D * (d["kv_lora"] + d["qk_rope"])
           + d["kv_lora"] + d["kv_lora"] * H * (d["qk_nope"] + d["v_head"])
           + H * d["v_head"] * D)
    expert = 3 * D * d["expert_ffn"]
    moe = D * d["num_experts"] + d["num_experts"] + (held + 1) * expert
    out = {"embed": d["vocab"] * D, "head": D * d["vocab"], "value": D + 1,
           "final_norm": D, "layers": 0}
    for kind in d["layers"]:
        mixer, ffn = kind.split("+")
        out["layers"] += 2 * D + (kda if mixer == "kda" else mla) + (
            3 * D * d["dense_ffn"] if ffn == "dense" else moe
        )
    out["total"] = sum(out.values())
    return out


def kda_carry_bytes(d: dict, num_envs: int) -> int:
    """What every KDA layer keeps in the carry, float32, read and written
    once: the state [H, dk, dv] and the short conv's tail [width - 1, 3 H dk]
    an env. What one decode step must move for the recurrence and for the
    reset at an episode's end, which passes over the same leaves."""
    layers = sum(k.startswith("kda") for k in d["layers"])
    n = d["kda_heads"] * d["kda_head_dim"]
    return 2 * 4 * layers * num_envs * (
        n * d["kda_head_dim"] + (d["conv_width"] - 1) * 3 * n
    )


def decode_bytes_per_step(d: dict, num_envs: int, attended: float,
                          weight_bytes: int = 2) -> float:
    """Bytes one decode step over ``num_envs`` envs must move: every weight
    it touches once at the products' width (the embedding's touched rows
    only; with 64 tokens choosing 8 of 256 every held expert is touched),
    the KDA states and conv tails read and written, the latent rows of the
    episodes in progress read and one row written."""
    p = parameters(d)
    weights = (p["total"] - p["embed"]) * weight_bytes + num_envs * d["hidden"] * 4
    latent = sum(k.startswith("mla") for k in d["layers"]) * (
        num_envs * (attended + 1) * (d["kv_lora"] + d["qk_rope"]) * weight_bytes
    )
    return weights + kda_carry_bytes(d, num_envs) + latent
