from benchmarks.program_record import programs_in_phase as read  # noqa: F401
