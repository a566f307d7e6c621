"""Scheduler and admission: the caller's share of the device's starved time
a step (``starved_caller_s`` over ``steps``): the time outside ``step()``
with the device empty, here the benchmark's own poll between steps, kept
apart so that the engine is not charged with it."""

from chipbench.metrics import _inflight


def read(run):
    return _inflight.per_step(run, "starved_caller_s")
