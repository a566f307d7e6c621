"""Step programs: device time of the programs run in a decode-only engine
step over that block's scan length, median over the traced steps. (The
trace gives every serving program the same name, ``jit_run``; a step in
which only a decode block ran is told from what the benchmark saw.)"""

import statistics

from chipbench.metrics._decode import pure_decode_steps


def read(run):
    steps = pure_decode_steps(run)
    per_token = [1e3 * sum(t1 - t0 for _, t0, t1 in modules) / n
                 for n, _, _, modules in steps or [] if modules]
    return statistics.median(per_token) if per_token else None
