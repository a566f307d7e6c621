"""Scheduler and admission: the engine's share of the device's starved time
a step: ``starved_emit_s + starved_admit_s + starved_prefill_s +
starved_dispatch_s`` over ``steps``, from the window's counters."""

from chipbench.metrics import _inflight


def read(run):
    return _inflight.per_step(run, *_inflight.ENGINE_PHASES)
