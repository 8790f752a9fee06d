"""A routed-expert layer that is told which experts it holds.

The router scores all ``num_experts`` and chooses ``top_k`` of them per
token, as the whole model does; this chip computes the part of the result
that its own experts give and adds nothing for the rest (on one chip the
layer runs without its exchange). No token is dropped and there is no
capacity factor. A static shape that can never overflow is every held
expert on every token (a decode step's few tokens are computed so); over a
fragment's tokens the experts' rows are gathered into buffers of eight
times the mean load (a router is not balanced, least of all a fresh one:
the fullest held expert of the benchmark's cell is sent four times the
mean), and a block that sends a held expert more than that is computed
densely instead (``lax.cond`` on the observed load): the same result at
four times the cost.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def route(x, router_kernel, router_bias, top_k: int, scale: float):
    """``x`` [N, D] -> (expert ids [N, k], weights [N, k]), in float32:
    sigmoid scores, the top k of score + correction bias (a buffer: no
    gradient reaches it), weights renormalised over the chosen scores."""
    with jax.named_scope("moe_router"):
        scores = jax.nn.sigmoid(jnp.matmul(
            x.astype(F32), router_kernel.astype(F32), precision=HIGHEST
        ))
        _, ids = jax.lax.top_k(
            scores + jax.lax.stop_gradient(router_bias.astype(F32)), top_k
        )
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
        weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        return ids, weights


def _expert_act(x, gate, up, dtype):
    """``silu(gate x) * up x`` -> [E, n, F] float32; the weights carry a
    leading expert axis ([E, D, F]); ``x`` is [E, n, D], or [n, D] where
    every expert sees the same rows."""
    def mm(w):
        return jnp.einsum(
            "enk,ekf->enf" if x.ndim == 3 else "nk,ekf->enf",
            x.astype(dtype), w, preferred_element_type=F32,
        )

    return jax.nn.silu(mm(gate)) * mm(up)


def _down(spec, act, down, dtype):
    return jnp.einsum(spec, act.astype(dtype), down, preferred_element_type=F32)


def held_token_weights(ids, weights, held):
    """[N, E_held]: the weight each held expert has for each token (0 where
    the token did not choose it)."""
    hit = ids[:, :, None] == jnp.asarray(held, ids.dtype)[None, None, :]
    return jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)


def held_experts(x, ids, weights, held, num_experts, gate, up, down, dtype):
    """sum over held experts of ``w E(x)``: [N, D] float32, and the number
    of tokens each held expert was sent [E_held]."""
    n_tokens, width = x.shape
    tw = held_token_weights(ids, weights, held)  # [N, E]
    load = jnp.sum(tw > 0, axis=0)
    # rows of an expert's gathered buffer: eight times its mean load, in 128s
    capacity = -(-8 * n_tokens * ids.shape[1] // (num_experts * 128)) * 128
    # cast once, outside the branches below
    gate, up, down = (w.astype(dtype) for w in (gate, up, down))

    def dense_block(x, tw):
        act = _expert_act(x, gate, up, dtype) * tw.T[..., None]
        return _down("enf,efd->nd", act, down, dtype)

    def dense(_):
        # every held expert on every token, 2,048 tokens at a time: at a
        # fragment's size [E, N, F] is never whole
        b = math.gcd(n_tokens, 2048)
        if b == n_tokens:
            return dense_block(x, tw)
        out = jax.lax.map(
            jax.checkpoint(lambda args: dense_block(*args)),
            (x.reshape(-1, b, width), tw.reshape(-1, b, len(held))),
        )
        return out.reshape(n_tokens, width)

    def gathered(_):
        # each expert's tokens, first come first: rows past its load point
        # at row N, which reads zeros and is dropped on the way back
        order = jnp.argsort(tw.T <= 0, axis=1, stable=True)[:, :capacity]
        rows = jnp.where(
            jnp.arange(capacity)[None, :] < load[:, None], order, n_tokens
        )
        xg = jnp.take(x, rows, axis=0, mode="fill", fill_value=0)  # [E, C, D]
        wg = jnp.take_along_axis(
            jnp.pad(tw.T, ((0, 0), (0, 1))), rows, axis=1
        )
        act = _expert_act(xg, gate, up, dtype) * wg[..., None]
        y = _down("enf,efd->end", act, down, dtype)
        return jnp.zeros((n_tokens, width), F32).at[rows.reshape(-1)].add(
            y.reshape(-1, width), mode="drop"
        )

    with jax.named_scope("moe_experts"):
        if capacity >= n_tokens:  # a decode step's few tokens
            out = dense(None)
        else:
            out = jax.lax.cond(jnp.max(load) <= capacity, gathered, dense, None)
    return out, load
