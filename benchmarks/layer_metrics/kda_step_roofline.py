from benchmarks.seq_readers import kda_step_roofline as read  # noqa: F401
