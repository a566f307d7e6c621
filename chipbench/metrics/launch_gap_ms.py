"""Device: a program call's way to the device. Over the traced window's
``pt.serve.call`` spans with ``drained`` (the device was known empty): the
first op of the execution paired with the call less the span's start; mean.
Nothing where the clock check finds a violation (``_inflight``)."""

from chipbench.metrics import _inflight


def read(run):
    acc = _inflight.of(run)
    if acc is None or not _inflight.clock_holds(acc):
        return None
    return _inflight.mean_gap_ms([g for _, g in acc.launch])
