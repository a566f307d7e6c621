"""Step programs: device time of a token step of the decode block, by the
block's own name. The executions of ``jit_pt_decode_block`` in the traced
window, whatever else ran in the same engine step, over their token steps
(the sampler's ``sort`` runs once a step: the cell's batches always sample).
``decode_step_ms`` reads only engine steps in which nothing but a decode
block ran and nobody finished; at 64 rows hardly a step is one."""

from chipbench.metrics import _program
from chipbench.metrics._scopes import leaves_of, token_steps

MODULE = "jit_pt_decode_block"


def read(run):
    prog = _program.of(run)
    if prog is None:
        return None
    leaves = leaves_of(prog, MODULE)
    steps = token_steps(leaves) if leaves else 0
    if not steps:
        return None
    runs = _program.executions(prog, MODULE)
    return 1e3 * sum(t1 - t0 for t0, t1 in runs) / steps
