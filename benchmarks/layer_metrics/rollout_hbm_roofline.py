from benchmarks.seq_readers import rollout_hbm_roofline as read  # noqa: F401
