from benchmarks.seq_readers import seq_step_mfu as read  # noqa: F401
