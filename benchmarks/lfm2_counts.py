"""Operations and bytes a token-level policy of the LFM2-MoE family needs,
computed from shapes (``configs/lfm2_moe_rl.json``'s ``model`` record) and
from what the program counted in the traced updates (the rows a query
attended, the assignments that landed on held experts).

As ``seq_counts.py``: 2 x multiply-accumulates of what the mathematics
requires, a backward pass costs twice a forward, nothing recomputed is
counted (the learner's rematerialised forward is not), and an
implementation's choice is not either: attention counts scores and weighted
values over the rows the token attends, not over the cache's capacity; the
expert layer counts the assignments routed to held experts, not the rows of
a buffer or of the dense side. Both forms of the policy are one algorithm
here, so the rollout's forward and the learner's cost the same.
"""

from __future__ import annotations


def _swiglu(width_in: int, width: int) -> int:
    return 2 * 3 * width_in * width


def conv_flops(d: dict) -> int:
    """Per token: the in-projection to B, C and x~, the out-projection, the
    two gates and the depthwise conv."""
    D = d["hidden"]
    return 2 * (D * 3 * D + D * D) + 2 * D + 2 * d["conv_width"] * D


def gqa_flops(d: dict, attended: float) -> float:
    """Per token: q, k, v and output projections, scores and weighted
    values over ``attended`` rows for each query head."""
    D, n_q, n_kv = d["hidden"], d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    products = D * n_q + 2 * D * n_kv + n_q * D
    return 2 * products + 2 * d["heads"] * attended * 2 * d["head_dim"]


def expert_flops(d: dict) -> int:
    """One token through one expert."""
    return _swiglu(d["hidden"], d["expert_ffn"])


def ffn_flops(d: dict, kind: str, held_per_token: float) -> float:
    """Per token. ``held_per_token``: token-expert assignments that land on
    a held expert, a layer (no shared expert)."""
    if kind == "dense":
        return _swiglu(d["hidden"], d["dense_ffn"])
    return 2 * d["hidden"] * d["num_experts"] + held_per_token * expert_flops(d)


def forward_flops_per_token(d: dict, attended: float, held_per_token: float) -> float:
    total = 2 * d["hidden"] * (d["vocab"] + 1)  # head and value head
    for kind in d["layers"]:
        mixer, ffn = kind.split("+")
        total += conv_flops(d) if mixer == "conv" else gqa_flops(d, attended)
        total += ffn_flops(d, ffn, held_per_token)
    return total


def train_flops_per_update(d: dict, tokens: int, attended: float,
                           held_per_token: float) -> float:
    """Rollout forward (x1) + learner forward and backward (x3)."""
    return tokens * 4 * forward_flops_per_token(d, attended, held_per_token)


def parameters(d: dict) -> dict:
    """Parameter counts by part, as ``Lfm2Policy.init`` builds them."""
    D, n_q, n_kv = d["hidden"], d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    conv = D * 3 * D + d["conv_width"] * D + D * D
    gqa = D * n_q + 2 * D * n_kv + 2 * d["head_dim"] + n_q * D
    moe = (D * d["num_experts"] + d["num_experts"]
           + len(d["held_experts"]) * 3 * D * d["expert_ffn"])
    out = {"embed": d["vocab"] * D, "head": D * d["vocab"], "value": D + 1,
           "final_norm": D, "layers": 0, "experts": 0}
    for kind in d["layers"]:
        mixer, ffn = kind.split("+")
        out["layers"] += 2 * D + (conv if mixer == "conv" else gqa) + (
            3 * D * d["dense_ffn"] if ffn == "dense" else moe
        )
        if ffn == "moe":
            out["experts"] += len(d["held_experts"]) * 3 * D * d["expert_ffn"]
    out["total"] = sum(v for k, v in out.items() if k != "experts")
    return out


def decode_bytes_per_step(d: dict, num_envs: int, attended: float,
                          weight_bytes: int = 2) -> float:
    """Bytes one decode step over ``num_envs`` envs must move: every weight
    it touches once at the products' width (the embedding's touched rows
    only; 128 tokens choosing 4 of 32 reach every held expert: each is sent
    16 on average), the key and value rows of the episodes in progress read
    and one row of each written, the conv tails read and written."""
    p = parameters(d)
    weights = (p["total"] - p["embed"]) * weight_bytes + num_envs * d["hidden"] * 4
    row = d["kv_heads"] * d["head_dim"] * weight_bytes
    cache = sum(k.startswith("gqa") for k in d["layers"]) * (
        num_envs * (attended + 1) * 2 * row
    )
    tails = sum(k.startswith("conv") for k in d["layers"]) * (
        2 * 4 * num_envs * (d["conv_width"] - 1) * d["hidden"]
    )
    return weights + cache + tails
