from benchmarks.moonlight_readers import moonlight_rollout_hbm_roofline as read  # noqa: F401
