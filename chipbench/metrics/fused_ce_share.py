"""Model: the fused cross-entropy's share of the train step. Leaf-op device
time whose name stack holds ``pt.fused_ce`` (forward, and backward as
``transpose(jvp(pt.fused_ce))``) over the leaf-op device time inside the
executions of ``jit_pt_train_step`` in the traced window."""

from chipbench.metrics import _program


def read(run):
    prog = _program.of(run)
    if prog is None:
        return None
    return _program.share(prog, "jit_pt_train_step", "pt.fused_ce")
