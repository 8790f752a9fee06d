from benchmarks.keye_readers import keye_rollout_hbm_roofline as read  # noqa: F401
