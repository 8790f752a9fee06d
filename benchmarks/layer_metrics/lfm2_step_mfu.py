from benchmarks.lfm2_readers import lfm2_step_mfu as read  # noqa: F401
