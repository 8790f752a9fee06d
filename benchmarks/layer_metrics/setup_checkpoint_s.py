from benchmarks.program_record import phase_seconds as read  # noqa: F401
