"""Operations and bytes a token-level policy of granite-4.0-h-micro needs,
computed from shapes (``configs/granite_h_rl.json``'s ``model`` record) and
from what the program counted in the traced updates (the rows of its
episode a query of the attention layer attended).

As ``moonlight_counts.py``: 2 x multiply-accumulates of what the mathematics
requires, a backward pass costs twice a forward, nothing recomputed is
counted. The two forms of the state-space recurrence are two algorithms,
each counted as it is written:

- the one-token form (the rollout) writes the state with an outer product
  and reads it out with ``C``: 2 x H P N multiply-accumulates a token;
- the chunked form (the learner) computes, a chunk of Q tokens, ``C B^T``
  (Q x Q x N), the masked pairs against ``delta u`` (Q x Q x H P), the
  chunk-initial state read out (Q x H P N) and the state handed over
  (Q x H P N), over the whole chunk's shapes.
"""

from __future__ import annotations


def _mamba_sizes(d: dict):
    inner = d["mamba_heads"] * d["mamba_head_dim"]
    return inner, inner + 2 * d["mamba_state"]


def _is_mamba(kind: str) -> bool:
    return kind.startswith("mamba")


def projection_flops(d: dict, kind: str) -> int:
    """Per token: a layer's projections, its conv and its SwiGLU."""
    D, F = d["hidden"], d["ffn"]
    ffn = 3 * D * F
    if _is_mamba(kind):
        inner, xbc = _mamba_sizes(d)
        return 2 * (D * (inner + xbc + d["mamba_heads"]) + d["conv_width"] * xbc
                    + inner * D + ffn)
    q, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    return 2 * (D * q + 2 * D * kv + q * D + ffn)


def ssd_step_flops(d: dict) -> int:
    """Per token: the state's write (outer product) and its read-out."""
    return 2 * 2 * d["mamba_heads"] * d["mamba_head_dim"] * d["mamba_state"]


def ssd_chunk_flops(d: dict, T: int) -> float:
    """Per token of a fragment of ``T``: the chunked form's four products
    over its chunks' shapes (Q = min(chunk, T))."""
    Q = min(d["chunk"], T)
    HP, N = d["mamba_heads"] * d["mamba_head_dim"], d["mamba_state"]
    return 2 * (Q * N + Q * HP + 2 * HP * N)


def attention_flops(d: dict, attended: float) -> float:
    """Per query: scores over ``attended`` rows and the weighted values."""
    return 2 * 2 * d["heads"] * d["head_dim"] * attended


def _rest_per_token(d: dict) -> float:
    """Per token and outside the mixers' own algebra: every layer's
    projections and SwiGLU, the tied head and the value head."""
    return (sum(projection_flops(d, kind) for kind in d["layers"])
            + 2 * d["hidden"] * (d["vocab"] + 1))


def _counts(d: dict):
    n_mamba = sum(_is_mamba(kind) for kind in d["layers"])
    return n_mamba, len(d["layers"]) - n_mamba


def rollout_flops(d: dict, tokens: int, attended: float) -> float:
    """The rollout's forward over ``tokens``, one token at a time."""
    n_mamba, n_attention = _counts(d)
    return tokens * (_rest_per_token(d) + n_mamba * ssd_step_flops(d)
                     + n_attention * attention_flops(d, attended))


def learner_forward_flops(d: dict, tokens: int, T: int, attended: float) -> float:
    """The learner's forward over ``tokens`` in fragments of ``T``."""
    n_mamba, n_attention = _counts(d)
    return tokens * (_rest_per_token(d) + n_mamba * ssd_chunk_flops(d, T)
                     + n_attention * attention_flops(d, attended))


def train_flops_per_update(d: dict, num_envs: int, T: int, attended: float) -> float:
    """Rollout forward (x1) + learner forward and backward (x3)."""
    tokens = num_envs * T
    return rollout_flops(d, tokens, attended) + 3 * learner_forward_flops(
        d, tokens, T, attended)


def parameters(d: dict) -> dict:
    """Parameter counts by part, as ``GraniteHPolicy.init`` builds them."""
    D, H = d["hidden"], d["mamba_heads"]
    inner, xbc = _mamba_sizes(d)
    q, kv = d["heads"] * d["head_dim"], d["kv_heads"] * d["head_dim"]
    mamba = (D * (inner + xbc + H) + d["conv_width"] * xbc + xbc + 3 * H + inner
             + inner * D)
    attention = D * q + 2 * D * kv + q * D
    ffn = 3 * D * d["ffn"]
    n_mamba, n_attention = _counts(d)
    n = len(d["layers"])
    out = {
        "embed": d["vocab"] * D, "value": D + 1, "final_norm": D,
        "layers": n * (2 * D + ffn) + n_mamba * mamba + n_attention * attention,
        "mamba": n_mamba * mamba, "attention": n_attention * attention,
        "ffn": n * ffn,
    }
    out["total"] = sum(out[k] for k in ("embed", "value", "final_norm", "layers"))
    return out


def ssd_carry_bytes(d: dict, num_envs: int) -> float:
    """What the Mamba layers keep in the carry (float32 states and conv
    tails), read and written once a step: bytes a step."""
    n_mamba, _ = _counts(d)
    _, xbc = _mamba_sizes(d)
    state = d["mamba_heads"] * d["mamba_head_dim"] * d["mamba_state"]
    tail = (d["conv_width"] - 1) * xbc
    return 2 * 4 * n_mamba * num_envs * (state + tail)


def decode_bytes_per_step(d: dict, num_envs: int, attended: float,
                          weight_bytes: int = 2) -> float:
    """Bytes one decode step over ``num_envs`` envs must move: every weight
    once at the products' width (the embedding whole: it is the head), the
    touched rows of the embedding in float32, the Mamba layers' carry read
    and written, and per attention layer and env the key and value rows of
    its episode up to ``len`` (``attended`` of them) and one of each
    written."""
    p = parameters(d)
    _, n_attention = _counts(d)
    row = d["kv_heads"] * d["head_dim"] * weight_bytes
    return (p["total"] * weight_bytes + num_envs * d["hidden"] * 4
            + ssd_carry_bytes(d, num_envs)
            + n_attention * num_envs * (attended + 1) * 2 * row)
