"""Operations and bytes a token-level policy of Keye-VL-2.0's language model
needs, computed from shapes (``configs/keye_moe_rl.json``'s ``model`` record)
and from what the program counted in the traced updates (the rows a query's
indexer scored, the rows it selected, the assignments that landed on held
experts).

As ``lfm2_counts.py``: 2 x multiply-accumulates of what the mathematics
requires, a backward pass costs twice a forward, nothing recomputed is
counted, and an implementation's choice is not either: attention counts
scores and weighted values over the rows a query SELECTED (the learner and
the rollout both compute every row under the selection's mask: that is
theirs), the indexer over the rows it scored (the rollout scores the cache's
whole capacity), the expert layer the assignments routed to held experts.
Both forms of the policy are one algorithm here.
"""

from __future__ import annotations


def _sizes(d: dict):
    return (d["hidden"], d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"],
            d["index_heads"] * d["index_dim"])


def attention_flops(d: dict, selected: float) -> float:
    """Per token: q, k, v and output projections, scores and weighted values
    over ``selected`` rows for each query head."""
    D, n_q, n_kv, _ = _sizes(d)
    products = D * n_q + 2 * D * n_kv + n_q * D
    return 2 * products + 2 * d["heads"] * selected * 2 * d["head_dim"]


def indexer_flops(d: dict, scored: float) -> float:
    """Per token: the indexer's three projections, then for each of
    ``scored`` rows its heads' products and their weighted sum."""
    D, _, _, n_i = _sizes(d)
    products = D * n_i + D * d["index_dim"] + D * d["index_heads"]
    return 2 * products + 2 * d["index_heads"] * scored * (d["index_dim"] + 1)


def expert_flops(d: dict) -> int:
    """One token through one expert."""
    return 2 * 3 * d["hidden"] * d["expert_ffn"]


def forward_flops_per_token(d: dict, scored: float, selected: float,
                            held_per_token: float) -> float:
    layer = (attention_flops(d, selected) + indexer_flops(d, scored)
             + 2 * d["hidden"] * d["num_experts"] + held_per_token * expert_flops(d))
    return len(d["layers"]) * layer + 2 * d["hidden"] * (d["vocab"] + 1)


def train_flops_per_update(d: dict, tokens: int, scored: float, selected: float,
                           held_per_token: float) -> float:
    """Rollout forward (x1) + learner forward and backward (x3)."""
    return tokens * 4 * forward_flops_per_token(d, scored, selected, held_per_token)


def parameters(d: dict) -> dict:
    """Parameter counts by part, as ``KeyePolicy.init`` builds them."""
    D, n_q, n_kv, n_i = _sizes(d)
    attention = D * n_q + 2 * D * n_kv + 2 * d["head_dim"] + n_q * D
    indexer = D * n_i + D * d["index_dim"] + 2 * d["index_dim"] + D * d["index_heads"]
    experts = len(d["held_experts"]) * 3 * D * d["expert_ffn"]
    n = len(d["layers"])
    out = {
        "embed": d["vocab"] * D, "head": D * d["vocab"], "value": D + 1,
        "final_norm": D,
        "layers": n * (2 * D + attention + indexer + D * d["num_experts"] + experts),
        "attention": n * attention, "indexer": n * indexer, "experts": n * experts,
    }
    out["total"] = sum(out[k] for k in ("embed", "head", "value", "final_norm", "layers"))
    return out


def decode_bytes_per_step(d: dict, num_envs: int, scored: float, selected: float,
                          weight_bytes: int = 2) -> float:
    """Bytes one decode step over ``num_envs`` envs must move: every weight it
    touches once at the products' width (the embedding's touched rows only; 16
    tokens choosing 8 of 128 reach 8 of the 16 held experts on average, and
    every held expert is counted: the dense side reads them all), and per
    layer and env the indexer-key rows scored, the key and value rows
    selected, and one row of each written."""
    p = parameters(d)
    weights = (p["total"] - p["embed"]) * weight_bytes + num_envs * d["hidden"] * 4
    row = d["kv_heads"] * d["head_dim"] * weight_bytes
    key = d["index_dim"] * weight_bytes
    cache = len(d["layers"]) * num_envs * (
        (scored + 1) * key + (selected + 1) * 2 * row)
    return weights + cache
