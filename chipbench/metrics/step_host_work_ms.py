"""Scheduler and admission: the host's own work per engine step over the
window: wall time of ``step()`` less the time inside ``pt.serve.wait``
(blocked on device values), per step, from the engine's counters."""


def read(run):
    s0, s1 = run.window["stats0"], run.window["stats1"]
    if any(k not in s for s in (s0, s1)
           for k in ("steps", "step_wall_s", "device_wait_s")):
        return None
    steps = s1["steps"] - s0["steps"]
    if steps <= 0:
        return None
    work = ((s1["step_wall_s"] - s0["step_wall_s"])
            - (s1["device_wait_s"] - s0["device_wait_s"]))
    return 1e3 * work / steps
