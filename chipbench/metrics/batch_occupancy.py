"""Scheduler + admission: occupied slots over max_batch, mean over the
window's steps."""


def read(run):
    occ = run.window["sampler"].occupancy
    return 100.0 * sum(occ) / len(occ) if occ else None
