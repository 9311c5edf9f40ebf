"""Compile set-up: seconds jax spent building programs before the window,
compiled or loaded from the persistent cache (jax.monitoring)."""


def read(run: dict):
    return float(sum(s for _, s in run["setup_programs"]))
