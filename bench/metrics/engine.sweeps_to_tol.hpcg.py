from bench.readers import sweeps_to_tol as read  # noqa: F401
