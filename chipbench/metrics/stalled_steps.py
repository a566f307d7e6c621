"""Scheduler and admission: the window's steps that built no program and
took over a second (``steps_over_1s``); ``steps_over_1s_wait_s`` against
``steps_over_1s_starved_s`` beside it says whether the device or the host
held them."""

from chipbench.metrics import _inflight


def read(run):
    d = _inflight.delta(run, "steps_over_1s")
    return None if d is None else float(d["steps_over_1s"])
