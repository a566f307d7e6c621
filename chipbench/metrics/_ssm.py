"""Shared by the readers of the state-space layers in the decode block (not
a metric): WHICH device ops are theirs, so that ``ssm_share`` and
``ssm_step_roofline`` sum one set.

Named: every leaf op whose name stack holds ``pt.ssm``. Unnamed: the
compiler's own copies of the layers' pools. On a v5e the step's fusion
writes a layer's new matrix state ``[slots, heads, head_dim, state]`` into
the compiler's second memory space and a ``copy-start`` / ``copy-done`` pair
writes it back to HBM (memory space assignment adds the pair after every
name is attached, so it carries no ``pt`` name and nothing in the program can
give it one); the conv window ``[slots, taps - 1, width]`` and the token's
``[slots, 1, width]`` are sliced the same way. They are told by their KIND
(``copy``, ``slice`` and their ``-start`` / ``-done``) and the EXACT
dimensions of their first result, which the configuration and the cell
give: no substring of an op's text. A kernel that moves the pool itself has
no such copies and is read by its name alone.
"""

import re

from chipbench.metrics import _program
from chipbench.ops import ssd

_OP = re.compile(r"^%?([a-z\-]+?)[.\d]* = \(?[a-z0-9]+\[([\d,]*)\]")
_STARTED_BY = re.compile(r"-done\(%?([\w.\-]+)\)")
KINDS = ("copy", "copy-start", "copy-done", "slice", "slice-start",
         "slice-done")


def pool_dims(run):
    """The dimensions, as the trace writes them, of the matrix state's pool,
    the conv window's and the token's slice of it."""
    cfg = run.cell.config
    dims = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"])
    rows = int(run.cell.spec["engine"]["max_batch"])
    width = ssd.conv_width(*dims)
    return {f"{rows},{dims[0]},{dims[1]},{dims[3]}",
            f"{rows},{cfg['conv_kernel'] - 1},{width}", f"{rows},1,{width}"}


def is_pool_copy(op, dims) -> bool:
    """An op under no ``pt`` name that copies or slices an array of one of
    ``dims`` (one under ``pt.ssm`` is counted by its name already)."""
    if _program.PT_NAME.search(op.stack):
        return False
    m = _OP.match(op.name)
    return bool(m) and m.group(1) in KINDS and m.group(2) in dims


def ops_of(run, leaves):
    """(named, copies) of a module's leaf ops: those under ``pt.ssm`` and
    the pools' unnamed copies."""
    dims = pool_dims(run)
    return ([o for o in leaves if "pt.ssm" in o.stack],
            [o for o in leaves if is_pool_copy(o, dims)])


def transfers(copies):
    """[(t0, t1)] of the copies as TRANSFERS: a ``-done`` is a wait, so its
    transfer runs from the start of the ``-start`` it names (the latest
    before it) to its own end; an op that is neither stands for itself."""
    started, out = {}, []
    for o in sorted(copies, key=lambda o: o.t0):
        m = _OP.match(o.name)
        kind, name = m.group(1), o.name.lstrip("%").split(" ", 1)[0]
        if kind.endswith("-start"):
            started[name] = o.t0
        elif kind.endswith("-done"):
            by = _STARTED_BY.search(o.name)
            out.append((started.pop(by.group(1), o.t0) if by else o.t0, o.t1))
        else:
            out.append((o.t0, o.t1))
    return out
