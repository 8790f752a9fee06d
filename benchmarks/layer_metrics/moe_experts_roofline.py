from benchmarks.lfm2_readers import moe_experts_roofline as read  # noqa: F401
