from benchmarks.keye_readers import keye_step_mfu as read  # noqa: F401
