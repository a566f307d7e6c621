"""Device: the attribution's own check. The traced window's idle time that
lies in none of: a starved interval (the end of a newest-call wait to the
next call's start), a launch gap, a read-back gap; over all idle time."""

from chipbench.metrics import _inflight


def read(run):
    acc = _inflight.of(run)
    if acc is None or acc.idle_s <= 0:
        return None
    return 100.0 * sum(b - a for a, b in acc.unexplained) / acc.idle_s
