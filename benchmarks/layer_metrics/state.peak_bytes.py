"""State: `memory_stats()["peak_bytes_in_use"]` on the fullest chip after
the window. Shows a gain bought with memory."""


def read(run: dict):
    return float(run["memory_peak_bytes"]) or None
