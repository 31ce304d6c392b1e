"""Device: `memory_stats()["peak_bytes_in_use"]` after the window, before
the reference runs."""


def read(run):
    b = run.get("memory_peak_bytes")
    return b / 1e9 if b else None
