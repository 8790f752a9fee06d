from benchmarks.granite_readers import granite_step_mfu as read  # noqa: F401
