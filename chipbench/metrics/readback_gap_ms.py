"""Device: the values' way back to the host. Over the traced window's
``pt.serve.wait`` spans whose ``seq`` was the newest call: the span's end
less the last op of the execution it waited for; mean. Nothing where the
clock check finds a violation (``_inflight``)."""

from chipbench.metrics import _inflight


def read(run):
    acc = _inflight.of(run)
    if acc is None or not _inflight.clock_holds(acc):
        return None
    return _inflight.mean_gap_ms([g for _, _, g in acc.readback])
