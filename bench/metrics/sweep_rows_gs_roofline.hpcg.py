from bench.readers import sweep_roofline_pct as read  # noqa: F401
