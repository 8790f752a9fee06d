from benchmarks.granite_readers import granite_rollout_hbm_roofline as read  # noqa: F401
