"""Shared by the readers of a model's scopes (not a metric): leaf-op device
time under any of several ``pt`` names, over a module's executions."""

from chipbench.metrics import _program


def leaves_of(prog, module: str):
    """Leaf ops inside the executions of ``module``, or None when it never
    ran or nothing inside carries a name stack."""
    runs = _program.executions(prog, module)
    if not runs:
        return None
    leaves = _program.leaf_ops(_program.ops_inside(prog.ops, runs))
    if not leaves or not any(o.stack for o in leaves):
        return None
    return leaves


def under(op, scopes) -> bool:
    """Whether the op's name stack, or a kernel's own name, holds one of
    ``scopes``."""
    return any(s in op.stack or s in op.name for s in scopes)


def token_steps(leaves) -> int:
    """Token steps among a decode block's leaf ops: the sampler's ``sort``
    runs once a step (a batch that samples; an all-greedy block has none
    and reads 0)."""
    return sum(1 for o in leaves if "pt.sampler" in o.stack
               and o.name.lstrip("%").startswith("sort"))


def share_of(run, module: str, scopes):
    """Device time (%) of the leaf ops under any of ``scopes`` (each op
    once) over all leaf ops inside ``module``'s executions; None when there
    is nothing to read or no op carries any of the names (a program without
    them)."""
    prog = _program.of(run)
    if prog is None:
        return None
    leaves = leaves_of(prog, module)
    if leaves is None:
        return None
    total = sum(o.t1 - o.t0 for o in leaves)
    mine = [o for o in leaves if under(o, scopes)]
    if total <= 0 or not mine:
        return None
    return 100.0 * sum(o.t1 - o.t0 for o in mine) / total


def counter_delta(run, *names):
    """The window's change of each engine counter, or None when the
    program has not got one of them."""
    s0, s1 = run.window.get("stats0"), run.window.get("stats1")
    if not s0 or not s1 or any(n not in s0 or n not in s1 for n in names):
        return None
    return [s1[n] - s0[n] for n in names]
