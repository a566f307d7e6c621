"""Device: peak_bytes_in_use + peak_bytes_reserved on the fullest chip
(live buffers plus the programs' temporaries; either alone under-reports)."""


def read(run):
    peak = run.window.get("memory_peak_bytes")     # read as the window closed
    return peak / 1e9 if peak else None
