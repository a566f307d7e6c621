"""Shared by the decode readers (not a metric): the traced engine steps in
which nothing but a decode block ran — every slot in use was decoding,
nothing could be admitted, nobody finished — each with the device events
that fell inside its ``bench.engine.step`` annotation."""

from chipbench.harness import trace


def pure_decode_steps(run):
    """[(block length, [(context before, tokens grown)], ops inside,
    modules inside)] or None without a trace."""
    red, log = run.trace, run.window.get("steps_log")
    if red is None or not log or not red.devices:
        return None
    dev = red.devices[sorted(red.devices)[0]]
    notes = [h for h in red.host if h[0] == "bench.engine.step"]
    out = []
    for (name, a, b), (n, grown, pure) in zip(notes, log):
        if pure and n > 0:
            out.append((n, grown, trace.inside(dev["ops"], a, b),
                        trace.inside(dev["modules"], a, b)))
    return out
