"""Shared arithmetic of the device readers (not a metric)."""

from chipbench.harness import trace


def idle_share(run):
    if run.trace is None or not run.trace.devices:
        return None
    used = sorted(run.trace.devices)[:run.cell.chips]
    busy = [trace.busy_seconds(run.trace.devices[d]["ops"]) for d in used]
    window = max(run.trace_window_s, 1e-9)
    return 100.0 * (1.0 - (sum(busy) / len(busy)) / window)

