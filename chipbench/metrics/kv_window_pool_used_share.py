"""Cache manager: the window page groups' pages that live sequences map,
over those groups' pools, a mean over the window's steps: the engine sums
the pages in use once a step (``window_pages_in_use_steps``; the harness's
own sampler knows the full group's allocator only, ``kv_pool_used_share``).
Nothing to read from an engine without the counter or without a window
group."""

from chipbench.metrics._scopes import counter_delta


def read(run):
    got = counter_delta(run, "window_pages_in_use_steps", "steps")
    if got is None or got[1] <= 0:
        return None
    s1 = run.window["stats1"]
    pool = sum(v for k, v in s1.items() if k.startswith("kv_pool_pages.")
               and k != "kv_pool_pages.full")
    if pool <= 0:
        return None
    return 100.0 * got[0] / (got[1] * pool)
