"""Operations and bytes the algorithm needs, computed from shapes.

XLA's ``compiled.cost_analysis()["flops"]`` counts a ``lax.scan`` body once,
whatever its length, so for a step that scans K updates around T rollout
steps it undercounts by an order of magnitude. These functions count what the
mathematics requires: 2 x multiply-accumulates of every convolution and
matrix product, forward; a backward pass costs twice a forward; nothing
recomputed is counted (``remat`` does not raise the number).
"""

from __future__ import annotations

import math
from typing import Sequence


def _same_pool(size: int, stride: int = 2) -> int:
    return math.ceil(size / stride)


def impala_cnn_forward_flops(
    obs_shape: Sequence[int], channels: Sequence[int], num_outputs: int,
    fc: int = 256,
) -> int:
    """IMPALA deep network (Espeholt et al. 2018, Fig. 3 right), one frame.

    Each section: 3x3 SAME conv, 3x3/2 SAME max-pool, two residual blocks of
    two 3x3 SAME convs; then FC ``fc`` and the heads (``num_outputs`` =
    actions + 1 value). Pooling, ReLU and adds are not counted.
    """
    h, w, c_in = obs_shape
    flops = 0
    for c_out in channels:
        flops += 2 * h * w * 9 * c_in * c_out
        h, w = _same_pool(h), _same_pool(w)
        flops += 4 * (2 * h * w * 9 * c_out * c_out)
        c_in = c_out
    flops += 2 * (h * w * c_in) * fc
    flops += 2 * fc * num_outputs
    return flops


def mlp_forward_flops(
    obs_shape: Sequence[int], hidden_sizes: Sequence[int], num_outputs: int
) -> int:
    width = math.prod(obs_shape)
    flops = 0
    for size in hidden_sizes:
        flops += 2 * width * size
        width = size
    return flops + 2 * width * num_outputs


def forward_flops(model: dict) -> int:
    """``model``: torso, obs_shape, num_actions, and channels or hidden_sizes
    (the keys a configuration file and the env's spec give)."""
    outputs = model["num_actions"] + 1
    if model["torso"] == "impala_cnn":
        return impala_cnn_forward_flops(
            model["obs_shape"], model["channels"], outputs
        )
    if model["torso"] == "mlp":
        return mlp_forward_flops(
            model["obs_shape"], model["hidden_sizes"], outputs
        )
    raise ValueError(f"no FLOP count for torso {model['torso']!r}")


def train_flops_per_env_frame(model: dict, rollout_on_device: bool) -> int:
    """Learner forward + backward (x3) and, where the rollout runs inside
    the step (Anakin), its forward (x1). The bootstrap column's forward
    (1/T of one forward) is left out."""
    return forward_flops(model) * (4 if rollout_on_device else 3)


def train_flops_per_call(
    model: dict, num_envs: int, unroll_len: int, updates_per_call: int,
    rollout_on_device: bool = True,
) -> int:
    return (
        train_flops_per_env_frame(model, rollout_on_device)
        * num_envs * unroll_len * updates_per_call
    )


def fused_vtrace_bytes(unroll_len: int, num_envs: int) -> int:
    """Bytes the fused V-trace kernel must move for one [T, B] fragment:
    five f32 [T, B] inputs and the [B] bootstrap in, three f32 [T, B]
    tiles out (``ops/pallas_scan.py fused_vtrace_pallas``)."""
    return 4 * (8 * unroll_len * num_envs + num_envs)


def fused_vtrace_flops(unroll_len: int, num_envs: int) -> int:
    """About 10 f32 operations per element (two TD forms, the recurrence,
    the reconstruction): far under the bytes bound on any TPU."""
    return 10 * unroll_len * num_envs
