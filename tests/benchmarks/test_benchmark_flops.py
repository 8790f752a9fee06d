"""The shape-derived FLOP count, and why it exists."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import flops

ATARI_IMPALA = {
    "torso": "impala_cnn", "obs_shape": (84, 84, 4), "channels": (16, 32, 32),
    "num_actions": 6,
}


def test_atari_impala_forward_is_108_mflop():
    assert flops.forward_flops(ATARI_IMPALA) == pytest.approx(108.4e6, rel=0.01)


def test_atari_impala_env_frame_is_433_mflop():
    # forward x1 in the rollout, forward + backward (x3) in the learner
    assert flops.train_flops_per_env_frame(ATARI_IMPALA, True) == pytest.approx(
        433e6, rel=0.01
    )
    assert flops.train_flops_per_env_frame(ATARI_IMPALA, False) == pytest.approx(
        325e6, rel=0.01
    )


def test_count_by_hand_for_a_small_net():
    # one section, 4x4x1 input, 2 channels: conv 2*16*9*1*2, pool to 2x2,
    # four convs 2*4*9*2*2 each, FC (2*2*2)->256, heads 256->(3+1)
    model = {"torso": "impala_cnn", "obs_shape": (4, 4, 1), "channels": (2,),
             "num_actions": 3}
    expected = 2 * 16 * 9 * 2 + 4 * (2 * 4 * 9 * 4) + 2 * 8 * 256 + 2 * 256 * 4
    assert flops.forward_flops(model) == expected


def test_mlp_count():
    model = {"torso": "mlp", "obs_shape": (6,), "hidden_sizes": (256, 256),
             "num_actions": 6}
    assert flops.forward_flops(model) == 2 * (6 * 256 + 256 * 256 + 256 * 7)


@pytest.mark.parametrize("k,t", [(1, 32), (8, 32), (8, 64), (16, 8)])
def test_linear_in_k_and_t(k, t):
    one = flops.train_flops_per_call(ATARI_IMPALA, 256, 1, 1)
    assert flops.train_flops_per_call(ATARI_IMPALA, 256, t, k) == one * k * t


def test_unknown_torso_is_an_error():
    with pytest.raises(ValueError):
        flops.forward_flops({"torso": "nature_cnn", "num_actions": 2})


def test_fused_vtrace_bytes():
    # five [T, B] f32 inputs + [B] bootstrap in, three [T, B] tiles out
    assert flops.fused_vtrace_bytes(32, 256) == 4 * (8 * 32 * 256 + 256)


def _scan_flops(length: int) -> float:
    def step(x):
        def body(c, _):
            return c @ c, None

        return jax.lax.scan(body, x, None, length=length)[0]

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    cost = jax.jit(step).lower(x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_cost_analysis_counts_a_scan_body_once():
    """Why the count comes from shapes: XLA's own count of a scan of length
    8 (or 32) equals that of length 1, so for a step that scans K updates
    around T rollout steps it is low by an order of magnitude."""
    one = _scan_flops(1)
    assert one >= 2 * 256 ** 3
    assert _scan_flops(8) == pytest.approx(one, rel=0.01)
    assert _scan_flops(32) == pytest.approx(one, rel=0.01)
