from bench.readers import us_per_update as read  # noqa: F401
