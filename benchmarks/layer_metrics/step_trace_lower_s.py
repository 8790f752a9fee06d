from benchmarks.program_record import compile_seconds_in_phase as read  # noqa: F401
