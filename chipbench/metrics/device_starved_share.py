"""Scheduler and admission: the share of the window in which the device was
KNOWN empty with work to do: the engine's ``device_starved_s`` (from the
read of the newest program's output to the next program call, every step of
the window, no profiler) over the window less the caller's intervals past
1 s (``caller_over_1s_s``: the profiler writing its trace out). A floor of
``device_idle.serve`` with the host at fault."""

from chipbench.metrics import _inflight


def read(run):
    d = _inflight.delta(run, "device_starved_s", "caller_over_1s_s")
    if d is None:
        return None
    window = run.window["window_s"] - d["caller_over_1s_s"]
    if window <= 0:
        return None
    return 100.0 * d["device_starved_s"] / window
