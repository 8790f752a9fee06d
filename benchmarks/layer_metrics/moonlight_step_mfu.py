from benchmarks.moonlight_readers import moonlight_step_mfu as read  # noqa: F401
