"""Tier-1 perf smoke: the overlapped zero-copy pipeline (config.overlap_h2d)
vs the legacy copy-and-stack path on a tiny pong_impala-shaped sebulba run.

Two guarantees, one A/B:
- SEMANTICS: both paths produce identical losses on a fixed seed (the
  slab drain feeds the learner the same bytes in the same order).
- PERFORMANCE: the overlapped path is not slower. Wall-clock on a shared
  1-core CI box is noisy, so the in-tree assertion keeps a generous margin
  (a strict comparison is a benchmark cell's to make: ROADMAP A2);
  a structural regression (overlap path serializing, slab waits on every
  fragment) still fails it.
"""

import time

import numpy as np

from asyncrl_tpu import make_agent
from asyncrl_tpu.configs import presets

N_UPDATES = 6


def _tiny_pong_config(overlap: bool):
    return presets.get("pong_impala").replace(
        backend="sebulba", host_pool="jax", num_envs=8, actor_threads=1,
        unroll_len=8, precision="f32", log_every=1, seed=3,
        hidden_sizes=(32, 32),
        # No mid-run publish: fragment content then depends only on the
        # seeds, never on the actor/learner thread race — the precondition
        # for the identical-losses assertion.
        actor_staleness=1_000_000,
        overlap_h2d=overlap,
    )


def _run(overlap: bool):
    cfg = _tiny_pong_config(overlap)
    steps = N_UPDATES * 8 * 8  # updates * num_envs * unroll_len
    agent = make_agent(cfg)
    try:
        # Untimed warm-up update amortizes jit compilation out of the A/B.
        agent.train(total_env_steps=8 * 8)
        t0 = time.perf_counter()
        history = agent.train(total_env_steps=8 * 8 + steps)
        elapsed = time.perf_counter() - t0
    finally:
        agent.close()
    losses = [h["loss"] for h in history]
    return losses, elapsed, history


def test_overlap_matches_legacy_losses_and_is_not_slower():
    losses_on, t_on, hist_on = _run(overlap=True)
    losses_off, t_off, hist_off = _run(overlap=False)
    # Second overlap run: the FIRST measurement in a process is
    # systematically slow (XLA/threadpool warm-up outliving the per-agent
    # jit warm-up), so the on-first ordering above would bias against the
    # overlap path; best-of-two removes the order effect.
    _, t_on2, _ = _run(overlap=True)
    t_on = min(t_on, t_on2)

    # Identical losses, fixed seed: same fragments, same update sequence.
    assert len(losses_on) == len(losses_off) > 0
    np.testing.assert_allclose(losses_on, losses_off, rtol=0, atol=0)

    # The new pipeline metrics must surface in the metrics window on both
    # paths (the overlap is provable from the output, not asserted).
    for window in (hist_on[-1], hist_off[-1]):
        assert "h2d_wait_s" in window and window["h2d_wait_s"] >= 0
        assert "h2d_bytes" in window and window["h2d_bytes"] > 0
        assert 0.0 <= window["learner_stall_frac"] <= 1.0
    assert "slab_reuse_waits" in hist_on[-1]

    # Not slower, with CI-noise slack (see module docstring).
    assert t_on <= 1.5 * t_off, (
        f"overlapped path took {t_on:.2f}s vs legacy {t_off:.2f}s"
    )
