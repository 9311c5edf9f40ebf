"""Junction + dispatch: step retraces (the engine's `compiles` counter) plus
backend compiles or cache loads (jax.monitoring) inside the measured window.
Expected 0: every shape was warmed in set-up."""


def read(run: dict):
    return float(run["window_retraces"] + len(run["window_programs"]))
