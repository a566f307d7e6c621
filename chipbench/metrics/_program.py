"""Shared by the readers of what the program names (not a metric): the
run's trace read by the ``pt`` names the program gives: ``pt.*`` host spans
with their nesting, module executions by name, and for every device op its
name stack.

On the chip an "XLA Ops" event is named by its HLO instruction text; the
name stack (``jit(pt_decode_block)/while/body/pt.sampler/sort``) is the
``tf_op`` stat of the op's *event metadata*, which ``jax.profiler``'s
``ProfileData`` does not return. So this walks the few protobuf fields of
the ``.xplane.pb`` itself (XSpace > XPlane > XLine > XEvent, XEventMetadata,
XStat; field numbers of tsl/profiler/protobuf/xplane.proto) and depends on
nothing outside chipbench. The file is found by the rule ``harness/trace.py``'s ``Tracer``
writes it by: ``<ROOT>/.chipbench_trace/<cell>/plugins/profile/*/*.xplane.pb``.

A reader that finds no name returns ``None`` ("nothing to read"): a program
without the names (the parent of the PR that brought them) gives nothing,
never a guess from a shape.

Run on a trace file, this prints device time by ``pt`` scope:

    python3 chipbench/metrics/_program.py <file.xplane.pb> [module name]
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if __name__ == "__main__":                 # run as a script too
    sys.path.insert(0, ROOT)
from chipbench.harness.trace import union  # noqa: E402
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# a name the program gave inside a module: "pt.sampler", "pt_paged_decode"
# (not the module's own, "jit(pt_decode_block)", which every op carries)
PT_NAME = re.compile(r"(?<!jit\()pt[._][A-Za-z0-9_.]*[A-Za-z0-9_]")
NO_SCOPE = "(no pt name)"


# ---- the protobuf wire format, as far as an xplane needs it ----------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; length-delimited
    values come as memoryviews, fixed64 as raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield num, wire, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def _stat(buf):
    """(metadata id, value) of an XStat; a ref_value comes as ("ref", id)."""
    key, val = 0, None
    for num, wire, v in _fields(buf):
        if num == 1:
            key = v
        elif num in (5, 6):
            val = _text(v)
        elif num == 7:
            val = ("ref", v)
        elif num in (3, 4):
            val = _signed(v) if num == 4 else v
    return key, val


def _event_metadata(buf):
    """(id, name, stats) of the value of an event_metadata map entry."""
    mid, name, stats = 0, "", []
    for num, wire, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            name = _text(v)
        elif num == 5:
            stats.append(_stat(v))
    return mid, name, stats


def _map_value(buf):
    for num, wire, v in _fields(buf):
        if num == 2:
            return v
    return b""


@dataclasses.dataclass
class Op:
    name: str            # the HLO instruction text (chip) or the op's name
    stack: str           # the name stack (tf_op), "" if the trace has none
    t0: float            # seconds, on the trace's clock
    t1: float


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float
    args: dict
    parent: "Span | None" = None


@dataclasses.dataclass
class Program:
    """One trace, by the program's names."""
    ops: list            # Op of device 0's "XLA Ops" line, by start
    modules: list        # (module name without its hash, t0, t1), by start
    spans: list          # pt.* host Spans, by start, each with its parent
    path: str = ""


def _plane(buf):
    name, lines, emeta, smeta = "", [], {}, {}
    for num, wire, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            mid, mname, stats = _event_metadata(_map_value(v))
            emeta[mid] = (mname, stats)
        elif num == 5:
            sid = sname = None
            for n2, _, v2 in _fields(_map_value(v)):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = _text(v2)
            smeta[sid] = sname
    return name, lines, emeta, smeta


def _line(buf):
    name, ts_ns, events = "", 0, []
    for num, wire, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            ts_ns = _signed(v)
        elif num == 4:
            events.append(v)
    return name, ts_ns, events


def _event(buf, ts_ns):
    mid = off = dur = 0
    stats = []
    for num, wire, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            off = _signed(v)
        elif num == 3:
            dur = _signed(v)
        elif num == 4:
            stats.append(v)
    # whole picoseconds first: an op that ends where the next begins must
    # compare so after the conversion too
    ps = ts_ns * 1000 + off
    return mid, ps * 1e-12, (ps + dur) * 1e-12, stats


def _resolve(val, smeta):
    return smeta.get(val[1], "") if isinstance(val, tuple) else val


def read(path: str) -> Program:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    ops, modules, spans = [], [], []
    device = None
    for num, wire, v in _fields(space):
        if num != 1:
            continue
        pname, lines, emeta, smeta = _plane(v)
        m = DEVICE_PLANE.match(pname)
        if m:
            # the first device, as the harness's readers take it
            if device is not None and int(m.group(1)) >= device:
                continue
            device, ops, modules = int(m.group(1)), [], []
        stacks = {}
        for mid, (mname, stats) in emeta.items():
            for key, val in stats:
                if smeta.get(key) == "tf_op":
                    stacks[mid] = _resolve(val, smeta) or ""
        for lbuf in lines:
            lname, ts_ns, events = _line(lbuf)
            if m and lname in (OPS_LINE, MODULES_LINE):
                for ebuf in events:
                    mid, t0, t1, _ = _event(ebuf, ts_ns)
                    ename = emeta.get(mid, ("", ()))[0]
                    if lname == OPS_LINE:
                        ops.append(Op(ename, stacks.get(mid, ""), t0, t1))
                    else:
                        modules.append((ename.split("(")[0], t0, t1))
            elif not m:
                # the Python tracer can leave 10^5 events on this plane:
                # only those named pt.* are decoded past their first field
                ours = {mid for mid, (n, _) in emeta.items()
                        if n.startswith("pt.")}
                for ebuf in events if ours else ():
                    if ebuf[0] == 0x08 and _varint(ebuf, 1)[0] not in ours:
                        continue
                    mid, t0, t1, stats = _event(ebuf, ts_ns)
                    if mid not in ours:
                        continue
                    ename = emeta[mid][0]
                    args = {}
                    for sbuf in stats:
                        key, val = _stat(sbuf)
                        args[smeta.get(key, str(key))] = _resolve(val, smeta)
                    spans.append(Span(ename, t0, t1, args))
    ops.sort(key=lambda o: (o.t0, -o.t1))
    modules.sort(key=lambda e: e[1])
    spans.sort(key=lambda s: (s.t0, -s.t1))
    open_ = []
    for s in spans:
        while open_ and open_[-1].t1 <= s.t0:
            open_.pop()
        s.parent = open_[-1] if open_ else None
        open_.append(s)
    return Program(ops=ops, modules=modules, spans=spans, path=path)


def trace_file(cell_name: str):
    found = sorted(glob.glob(os.path.join(
        ROOT, ".chipbench_trace", cell_name, "plugins", "profile", "*",
        "*.xplane.pb")))
    return found[-1] if found else None


_CACHE = {}


def of(run):
    """The Program of a run's trace, or None: no trace, no file, or a trace
    in which nothing carries a ``pt`` name (the program has none, or a CPU
    rehearsal's trace holds no name stacks)."""
    if run.trace is None:
        return None
    path = trace_file(run.cell.name)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        try:
            _CACHE[key] = read(path)
        except (OSError, ValueError, IndexError):
            _CACHE[key] = None
    return _CACHE[key]


# ---- arithmetic on a Program -------------------------------------------------

def executions(prog: Program, module: str):
    """[(t0, t1)] of the executions of the module of that name
    (``jit_pt_decode_block``)."""
    return [(t0, t1) for name, t0, t1 in prog.modules if name == module]


def leaf_ops(ops):
    """Ops that hold no other op: a ``while`` or a ``call`` spans the ops
    of its body, which would count twice."""
    out, stack = [], []
    for o in ops:                        # by start, the longer first
        while stack and stack[-1][0].t1 <= o.t0:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([o, False])
    out += [o for o, has_child in stack if not has_child]
    return out


def ops_inside(ops, spans):
    """Ops whose middle lies in one of the sorted, disjoint (t0, t1)."""
    out, i = [], 0
    for o in ops:
        mid = 0.5 * (o.t0 + o.t1)
        while i < len(spans) and spans[i][1] < mid:
            i += 1
        if i < len(spans) and spans[i][0] <= mid:
            out.append(o)
    return out


def scope_of(op: Op) -> str:
    """The innermost ``pt`` name of an op: of its name stack, else of its
    instruction's name (a Pallas kernel is named after its ``name=``)."""
    found = PT_NAME.findall(op.stack)
    if found:
        return found[-1]
    m = PT_NAME.match(op.name.lstrip("%"))
    return m.group(0).rstrip(".0123456789") if m else NO_SCOPE


def share(prog: Program, module: str, scope: str):
    """Leaf-op device time whose name stack holds ``scope``, as a share
    (%) of the leaf-op device time inside the executions of ``module``.
    None when the module never ran or nothing inside carries a name."""
    runs = executions(prog, module)
    if not runs:
        return None
    leaves = leaf_ops(ops_inside(prog.ops, runs))
    total = sum(o.t1 - o.t0 for o in leaves)
    if total <= 0 or not any(o.stack for o in leaves):
        return None
    return 100.0 * sum(o.t1 - o.t0 for o in leaves
                       if scope in o.stack) / total


def idle_gaps(prog: Program):
    """[(t0, t1)] where device 0 ran no op, between its first and last."""
    busy = union([(o.t0, o.t1) for o in prog.ops])
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def innermost(prog: Program, t: float):
    """The innermost pt.* span open at ``t``, or None."""
    best = None
    for s in prog.spans:
        if s.t0 > t:
            break
        if s.t1 >= t and (best is None or s.t0 >= best.t0):
            best = s
    return best


def by_scope(prog: Program, module: str | None = None):
    """[(scope, seconds)] of leaf-op device time by innermost pt name, the
    largest first; inside the executions of ``module`` when given."""
    ops = prog.ops if module is None else ops_inside(
        prog.ops, executions(prog, module))
    tot = {}
    for o in leaf_ops(ops):
        k = scope_of(o)
        tot[k] = tot.get(k, 0.0) + (o.t1 - o.t0)
    return sorted(tot.items(), key=lambda kv: -kv[1])


_HLO = re.compile(r"^%?([\w.\-]+?)[.\d]* = \(?([a-z0-9]+\[[^\]]*\])?")


def by_scope_and_op(prog: Program, module: str | None = None, k: int = 12):
    """[(scope, instruction name without its number + result shape, count,
    seconds)]: the ``k`` largest kinds of leaf op, each under its scope."""
    ops = prog.ops if module is None else ops_inside(
        prog.ops, executions(prog, module))
    tot = {}
    for o in leaf_ops(ops):
        m = _HLO.match(o.name)
        key = (scope_of(o),
               " ".join(filter(None, m.groups())) if m else o.name[:60])
        n, t = tot.get(key, (0, 0.0))
        tot[key] = (n + 1, t + o.t1 - o.t0)
    return sorted(((sc, lb, n, t) for (sc, lb), (n, t) in tot.items()),
                  key=lambda x: -x[3])[:k]


def gaps_by_span(prog: Program, longer_than: float = 0.0):
    """[(span name or "(none)", seconds, count)] of device idle time by the
    innermost pt.* span at each gap's middle."""
    tot = {}
    for a, b in idle_gaps(prog):
        if b - a <= longer_than:
            continue
        s = innermost(prog, 0.5 * (a + b))
        k = s.name if s else "(none)"
        t, c = tot.get(k, (0.0, 0))
        tot[k] = (t + b - a, c + 1)
    return sorted(((k, t, c) for k, (t, c) in tot.items()),
                  key=lambda x: -x[1])


def describe(prog: Program, module: str | None = None) -> str:
    """Device time by pt scope with a row for what carries none, the
    modules, and how the pt.* host spans tile their steps."""
    out = []
    mods = {}
    for name, t0, t1 in prog.modules:
        n, t = mods.get(name, (0, 0.0))
        mods[name] = (n + 1, t + t1 - t0)
    out.append("modules: " + "; ".join(
        f"{k} x{n} {t * 1e3:.2f}ms" for k, (n, t) in
        sorted(mods.items(), key=lambda kv: -kv[1][1])))
    rows = by_scope(prog, module)
    total = sum(t for _, t in rows) or 1.0
    out.append(f"device time by pt scope"
               f"{' inside ' + module if module else ''} "
               f"(leaf ops, {total * 1e3:.2f}ms):")
    out += [f"  {k:28s} {t * 1e3:10.3f}ms {100 * t / total:6.2f}%"
            for k, t in rows]
    out.append("largest kinds of op, each under its scope:")
    out += [f"  {sc:20s} {lb:44s} x{n:<6d} {t * 1e3:10.3f}ms"
            for sc, lb, n, t in by_scope_and_op(prog, module)]
    for root in ("pt.serve.step", "pt.train.step"):
        steps = [s for s in prog.spans if s.name == root]
        if not steps:
            continue
        wall = sum(s.t1 - s.t0 for s in steps)
        kids = {}
        for s in prog.spans:
            if s.parent is not None and s.parent.name == root:
                kids[s.name] = kids.get(s.name, 0.0) + (s.t1 - s.t0)
        out.append(f"{root} x{len(steps)} {wall * 1e3:.2f}ms; self "
                   f"{100 * (1 - sum(kids.values()) / max(wall, 1e-12)):.2f}%"
                   f"; children: " + "; ".join(
                       f"{k} {100 * t / max(wall, 1e-12):.2f}%"
                       for k, t in sorted(kids.items(), key=lambda kv: -kv[1])))
    gaps = gaps_by_span(prog, 1e-3)
    out.append("idle gaps over 1 ms by innermost pt span: " + ("; ".join(
        f"{k} {t * 1e3:.2f}ms x{c}" for k, t, c in gaps) or "none"))
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(read(sys.argv[1]),
                   sys.argv[2] if len(sys.argv) > 2 else None))
