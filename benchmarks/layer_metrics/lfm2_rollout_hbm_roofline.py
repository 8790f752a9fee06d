from benchmarks.lfm2_readers import lfm2_rollout_hbm_roofline as read  # noqa: F401
