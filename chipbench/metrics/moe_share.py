"""Model: the routed-expert layers' share of the decode block. Leaf-op
device time whose name stack holds ``pt.moe`` (router, dispatch, the experts'
matmuls, the combine) over the leaf-op device time inside the executions of
``jit_pt_decode_block`` in the traced window."""

from chipbench.metrics._scopes import share_of


def read(run):
    return share_of(run, "jit_pt_decode_block", ("pt.moe",))
