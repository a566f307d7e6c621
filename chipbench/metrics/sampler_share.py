"""Step programs: the sampler's share of the decode block. Leaf-op device
time whose name stack holds ``pt.sampler`` (the key folding, the sort, the
nucleus cut, the draw) over the leaf-op device time inside the executions
of ``jit_pt_decode_block`` in the traced window."""

from chipbench.metrics import _program


def read(run):
    prog = _program.of(run)
    if prog is None:
        return None
    return _program.share(prog, "jit_pt_decode_block", "pt.sampler")
