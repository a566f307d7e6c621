"""Step programs: what a decode block costs whatever its length. Per
execution of ``jit_pt_decode_block``: its device time less that of its
token loop (the ``while`` ops at the program's top level), median over the
executions that lie wholly in the traced window: one whose top-level ops do
not reach from its start to its end (cut at the trace's edge) is left out.
An execution without a ``while`` (a block of one token, unrolled) gives
nothing."""

import statistics

from chipbench.metrics import _program


def _is_while(op) -> bool:
    return op.name.lstrip("%").startswith("while")


def read(run):
    prog = _program.of(run)
    if prog is None:
        return None
    fixed = []
    for t0, t1 in _program.executions(prog, "jit_pt_decode_block"):
        loop, first, end = 0.0, None, t0
        for op in _program.ops_inside(prog.ops, [(t0, t1)]):
            if op.t0 >= end:             # top level: inside no earlier op
                first = op.t0 if first is None else first
                end = op.t1
                if _is_while(op):
                    loop += op.t1 - op.t0
        slack = max(2e-6, 1e-3 * (t1 - t0))
        if first is None or first - t0 > slack or t1 - end > slack:
            continue                     # cut at the trace's edge
        if loop > 0:
            fixed.append(1e3 * ((t1 - t0) - loop))
    return statistics.median(fixed) if fixed else None
