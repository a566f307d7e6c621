"""Model: the Mamba-2 layers' share of the decode block: leaf-op device time
whose name stack holds ``pt.ssm`` (the projections, the convolution and its
window, the state's step, the gated norm) plus the compiler's unnamed copies
of the layers' pools (``metrics/_ssm.py``: the new state's write-back is one,
a tenth of the block), over the leaf-op device time inside the executions of
``jit_pt_decode_block`` in the traced window. The same ops as
``ssm_step_roofline`` takes, with the projections and the norm."""

from chipbench.metrics import _program, _ssm
from chipbench.metrics._scopes import leaves_of


def read(run):
    prog = _program.of(run)
    if prog is None:
        return None
    leaves = leaves_of(prog, "jit_pt_decode_block")
    if leaves is None:
        return None
    named, copies = _ssm.ops_of(run, leaves)
    total = sum(o.t1 - o.t0 for o in leaves)
    if total <= 0 or not named:
        return None
    return 100.0 * sum(o.t1 - o.t0 for o in named + copies) / total
