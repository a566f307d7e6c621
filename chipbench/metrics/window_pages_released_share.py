"""Cache manager: of the window-group pages mapped fresh in the window
(``window_pages_allocated``), the share that a sequence gave back while it
lived, the pages that fell behind its window (``window_pages_released``):
0 says nothing comes back before a sequence ends. Nothing to read from an
engine without the counters or that mapped no window page."""

from chipbench.metrics._scopes import counter_delta


def read(run):
    got = counter_delta(run, "window_pages_released",
                        "window_pages_allocated")
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
