"""Cache manager: blocks in use over blocks in the pool, mean over the
window's steps."""


def read(run):
    used = run.window["sampler"].pool_used
    return 100.0 * sum(used) / len(used) if used else None
