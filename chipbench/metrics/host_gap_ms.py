"""Scheduler and admission: device idle time the host's own work explains.
The idle gaps of the traced window (between the device's first and last
op) that fall, by their middle, inside a ``pt.serve.*`` span other than
``pt.serve.wait`` (innermost span), summed, per ``pt.serve.step`` in that
span of time."""

from chipbench.metrics import _program


def read(run):
    prog = _program.of(run)
    if prog is None or not prog.ops:
        return None
    lo, hi = prog.ops[0].t0, max(o.t1 for o in prog.ops)
    steps = [s for s in prog.spans
             if s.name == "pt.serve.step" and s.t1 > lo and s.t0 < hi]
    if not steps:
        return None
    idle = 0.0
    for a, b in _program.idle_gaps(prog):
        s = _program.innermost(prog, 0.5 * (a + b))
        if s is not None and s.name.startswith("pt.serve.") \
                and s.name != "pt.serve.wait":
            idle += b - a
    return 1e3 * idle / len(steps)
