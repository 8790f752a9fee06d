"""Operations and bytes a token-level policy of Moonlight-16B-A3B needs,
computed from shapes (``configs/moonlight_rl.json``'s ``model`` record) and
from what the program counted in the traced updates (the rows of its
episode a query attended, the cached rows a fragment needed, the
assignments that landed on held experts).

As ``keye_counts.py``: 2 x multiply-accumulates of what the mathematics
requires, a backward pass costs twice a forward, nothing recomputed is
counted, and an implementation's choice is not either. The two forms of the
latent attention are two algorithms, each counted as the least it needs:

- the one-token form (the rollout) absorbs ``kv_b`` into the query and the
  output, and attends over the latent rows of its episode;
- the fragment form (the learner) up-projects each row of an episode a
  fragment reads ONCE (the cached rows of the episodes in progress and the
  fragment's own) and attends over the keys and values of the rows of its
  episode. The program up-projects the cache's whole capacity every
  fragment, and computes every row under a mask: that is its own.
"""

from __future__ import annotations


def _sizes(d: dict):
    return (d["hidden"], d["mla_heads"], d["qk_nope"], d["qk_rope"], d["v_head"],
            d["kv_lora"])


def projection_flops(d: dict) -> int:
    """Per token: the query, the latent and rope key, and the output."""
    D, H, dn, dr, dv, lora = _sizes(d)
    return 2 * (D * H * (dn + dr) + D * (lora + dr) + H * dv * D)


def up_projection_flops(d: dict) -> int:
    """Per row: the latent into each head's key and value (``kv_b``)."""
    D, H, dn, dr, dv, lora = _sizes(d)
    return 2 * lora * H * (dn + dv)


def attention_flops(d: dict, attended: float) -> float:
    """Per query of the fragment form: scores over ``attended`` keys of
    nope + rope and the weighted values, each head."""
    D, H, dn, dr, dv, lora = _sizes(d)
    return 2 * H * attended * (dn + dr + dv)


def absorbed_flops(d: dict, attended: float) -> float:
    """Per query of the one-token form: the query into the latent, scores
    over ``attended`` latent rows and rope keys, the weighted latent rows,
    and out of the latent, each head."""
    D, H, dn, dr, dv, lora = _sizes(d)
    return 2 * H * (dn * lora + attended * (lora + dr) + attended * lora + lora * dv)


def ffn_flops(d: dict, kind: str, held_per_token: float) -> float:
    """Per token: the dense SwiGLU, or the router, the shared experts and
    ``held_per_token`` assignments to held experts."""
    D = d["hidden"]
    if kind.endswith("+dense"):
        return 2 * 3 * D * d["dense_ffn"]
    return (2 * D * d["num_experts"] + 2 * 3 * D * d["shared_ffn"]
            + held_per_token * 2 * 3 * D * d["expert_ffn"])


def _rest_per_token(d: dict, held_per_token: float) -> float:
    """Per token and outside the attention: every layer's projections and
    feed-forward, the head and the value head."""
    return (sum(projection_flops(d) + ffn_flops(d, kind, held_per_token)
                for kind in d["layers"]) + 2 * d["hidden"] * (d["vocab"] + 1))


def rollout_flops(d: dict, tokens: int, attended: float,
                  held_per_token: float) -> float:
    """The rollout's forward over ``tokens``, one token at a time."""
    per_token = _rest_per_token(d, held_per_token) + len(d["layers"]) * (
        absorbed_flops(d, attended))
    return tokens * per_token


def learner_forward_flops(d: dict, num_envs: int, T: int, attended: float,
                          cached: float, held_per_token: float) -> float:
    """The learner's forward over a fragment of ``num_envs`` x ``T``:
    each env's ``cached`` + T rows up-projected once a layer."""
    tokens = num_envs * T
    return (tokens * (_rest_per_token(d, held_per_token)
                      + len(d["layers"]) * attention_flops(d, attended))
            + len(d["layers"]) * num_envs * (cached + T) * up_projection_flops(d))


def train_flops_per_update(d: dict, num_envs: int, T: int, attended: float,
                           cached: float, held_per_token: float) -> float:
    """Rollout forward (x1) + learner forward and backward (x3)."""
    return (rollout_flops(d, num_envs * T, attended, held_per_token)
            + 3 * learner_forward_flops(d, num_envs, T, attended, cached,
                                        held_per_token))


def parameters(d: dict) -> dict:
    """Parameter counts by part, as ``MoonlightPolicy.init`` builds them."""
    D, H, dn, dr, dv, lora = _sizes(d)
    attention = D * H * (dn + dr) + D * (lora + dr) + lora + lora * H * (dn + dv) + H * dv * D
    experts = len(d["held_experts"]) * 3 * D * d["expert_ffn"]
    shared = 3 * D * d["shared_ffn"]
    router = D * d["num_experts"] + d["num_experts"]
    dense = 3 * D * d["dense_ffn"]
    n_dense = sum(kind.endswith("+dense") for kind in d["layers"])
    n_moe = len(d["layers"]) - n_dense
    n = len(d["layers"])
    out = {
        "embed": d["vocab"] * D, "head": D * d["vocab"], "value": D + 1,
        "final_norm": D,
        "layers": n * (2 * D + attention) + n_dense * dense
        + n_moe * (experts + shared + router),
        "attention": n * attention, "dense": n_dense * dense,
        "experts": n_moe * experts, "shared": n_moe * shared, "router": n_moe * router,
    }
    out["total"] = sum(out[k] for k in ("embed", "head", "value", "final_norm", "layers"))
    return out


def decode_bytes_per_step(d: dict, num_envs: int, attended: float,
                          weight_bytes: int = 2) -> float:
    """Bytes one decode step over ``num_envs`` envs must move: every weight it
    touches once at the products' width (the embedding's touched rows only;
    every held expert counted: the dense side reads them all), and per layer
    and env the latent rows of its episode up to ``len`` (``attended`` of
    them) and one row written."""
    p = parameters(d)
    weights = (p["total"] - p["embed"]) * weight_bytes + num_envs * d["hidden"] * 4
    row = (d["kv_lora"] + d["qk_rope"]) * weight_bytes
    return weights + len(d["layers"]) * num_envs * (attended + 1) * row
