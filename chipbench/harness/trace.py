"""Take a device trace in the middle of the window and reduce it.

``Tracer`` starts ``jax.profiler`` once ``start_s`` of the window have
passed and stops it ``length_s`` later. ``reduce`` reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` and gives, per device plane, the op
intervals (line "XLA Ops"), the program executions (line "XLA Modules") and,
from the host plane, the benchmark's own ``bench.*`` annotations.

Everything downstream (busy and idle share, kernel times, the breakdown)
is arithmetic on those lists, checked in ``chipbench/tests`` on a recorded
trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil

from .clock import now

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_SUFFIX = re.compile(r"[.\-_]?\d+$")


class Tracer:
    def __init__(self, out_dir: str, start_s: float, length_s: float):
        self.dir, self.start_s, self.length_s = out_dir, start_s, length_s
        self.state = "waiting"
        self.t_start = self.t_stop = 0.0

    def tick(self, elapsed: float):
        import jax

        if self.state == "waiting" and elapsed >= self.start_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.t_start = now()
            self.state = "tracing"
        elif self.state == "tracing" and \
                now() - self.t_start >= self.length_s:
            self.close()

    def close(self):
        import jax

        if self.state == "tracing":
            self.t_stop = now()
            jax.profiler.stop_trace()
            self.state = "done"

    @property
    def window_s(self) -> float:
        return self.t_stop - self.t_start

    def file(self):
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


@dataclasses.dataclass
class Reduced:
    devices: dict        # device index -> {"ops": [(name, t0, t1)], "modules": [...]}
    host: list           # (name, t0, t1) of bench.* annotations, seconds
    lines: dict          # plane name -> {line name: event count}


def reduce(path: str, cpu_rehearsal: bool = False) -> Reduced:
    """``cpu_rehearsal``: a CPU trace has no device plane; the XLA client's
    host threads then stand in for device 0, to rehearse the control flow."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, lines = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        seen = lines.setdefault(plane.name, {})
        for line in plane.lines:
            events = [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for e in line.events]
            seen[line.name] = len(events)
            if m and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                devices.setdefault(int(m.group(1)),
                                   {"ops": [], "modules": []})[key] = events
            elif not m:
                host += [e for e in events if e[0].startswith("bench.")]
                if cpu_rehearsal and line.name.startswith("tf_XLAPjRt"):
                    devices.setdefault(0, {"ops": [], "modules": []})[
                        "ops"] += [e for e in events if e[2] > e[1]]
    host.sort(key=lambda e: e[1])
    return Reduced(devices=devices, host=host, lines=lines)


def union(intervals):
    """Merged, sorted [(t0, t1)] of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(ops) -> float:
    return sum(b - a for a, b in union([(t0, t1) for _, t0, t1 in ops]))


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: events of one kind under one name."""
    return _SUFFIX.sub("", name) or name


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[^\]]*\])?")
PALLAS = 'custom_call_target="tpu_custom_call"'


def op_label(name: str) -> str:
    """A short label for an event of the "XLA Ops" line, whose name is the
    whole HLO instruction: the instruction's name without its number, its
    (first) result shape, and a mark on Pallas kernels. The same op of
    every layer of an unrolled model falls under one label."""
    m = _HLO.match(name)
    if not m:
        return op_family(name)[:80]
    label = op_family(m.group(1))
    if m.group(2):
        label += " " + m.group(2)
    if PALLAS in name:
        label += " [tpu_custom_call]"
    return label[:96]


def is_pallas(name: str) -> bool:
    return PALLAS in name




def top_ops(ops, k: int = 10):
    tot = {}
    for name, t0, t1 in ops:
        f = op_label(name)
        tot[f] = tot.get(f, 0.0) + (t1 - t0)
    return sorted(([n, s] for n, s in tot.items()), key=lambda x: -x[1])[:k]


def leaf_ops(ops):
    """Ops that contain no other op: on the "XLA Ops" line a ``while`` or a
    ``call`` spans the ops of its body, which would count twice."""
    ev = sorted(ops, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for e in ev:
        while stack and stack[-1][0][2] <= e[1]:
            top, has_child = stack.pop()
            if not has_child:
                out.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    out += [e for e, has_child in stack if not has_child]
    return out


def inside(events, t0: float, t1: float):
    """Events that lie within [t0, t1] (by their middle)."""
    return [e for e in events if t0 <= 0.5 * (e[1] + e[2]) <= t1]


def idle_gaps(ops, host, t0: float, t1: float, k: int = 10):
    """Idle time of one device inside [t0, t1], by the innermost bench.*
    annotation open at the middle of each gap; "(none)" where the host was
    in none."""
    busy = union([(a, b) for _, a, b in ops])
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    tot = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a <= 0:
            continue
        mid = 0.5 * (a + b)
        inside = [h for h in host if h[1] <= mid <= h[2]]
        name = min(inside, key=lambda h: h[2] - h[1])[0] if inside \
            else "(none)"
        tot[name] = tot.get(name, 0.0) + (b - a)
    return sorted(([n, s] for n, s in tot.items()), key=lambda x: -x[1])[:k]


def summary(red: Reduced, n_devices: int) -> dict:
    """busy_s averaged over the chips used, the traced span, the breakdown."""
    used = sorted(red.devices)[:n_devices]
    if not used:
        return {}
    busy = [busy_seconds(red.devices[d]["ops"]) for d in used]
    d0 = red.devices[used[0]]
    ops0 = d0["ops"]
    if not ops0:
        return {}
    t0 = min(e[1] for e in ops0)
    t1 = max(e[2] for e in ops0)
    return {"busy_s": sum(busy) / len(busy), "span_s": t1 - t0,
            "device_ops": top_ops(leaf_ops(ops0)),
            "idle_gaps": idle_gaps(ops0, red.host, t0, t1)}


def describe(red: Reduced, k: int = 25) -> str:
    """What a trace holds, for a look by hand."""
    out = []
    for plane, ls in red.lines.items():
        out.append(f"plane {plane}: " + ", ".join(
            f"{n}={c}" for n, c in ls.items()))
    for d, dev in red.devices.items():
        for kind in ("modules", "ops"):
            out.append(f"device {d} top {kind}: " + "; ".join(
                f"{n} {s * 1e3:.2f}ms" for n, s in top_ops(dev[kind], k)))
    for d, dev in red.devices.items():
        raw = sorted(leaf_ops(dev["ops"]), key=lambda e: e[1] - e[2])[:6]
        out += [f"device {d} longest op: {(t1 - t0) * 1e3:.3f}ms {n[:600]}"
                for n, t0, t1 in raw]
    names = sorted({h[0] for h in red.host})
    out.append("host annotations: " + ", ".join(names))
    return "\n".join(out)
