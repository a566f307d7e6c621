"""Scratch (never a run): a serving cell as ``run.py`` runs it, with the
engine's in-flight ledger printed beside the result (PR 38): the window's
difference of every ledger counter, a step; floor and ceiling of the
device's idle time with the host at fault; what ``jax.profiler``'s
``start_trace`` and ``stop_trace`` took; and, from a traced run's file,
``metrics/_inflight.py``'s account (launch and read-back gaps by program,
the clock check, the idle time nothing explains) beside
``metrics/_program.py``'s gaps by span. A traced cell's file is too large to
bring back from the chip's machine, so the readers run there.

    python3 chipbench/scratch/inflight_report.py <cell> <seed> <seconds> \
        <trace 0|1> [recorder] [rehearse]

``recorder`` lays a ``TraceRecorder`` on the engine (the harness passes no
``tracer=``), for the cost of the spans with a recorder attached.
"""

import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

LEDGER = ("device_starved_s", "starved_emit_s", "starved_admit_s",
          "starved_prefill_s", "starved_dispatch_s", "starved_caller_s",
          "device_maybe_starved_s", "drains", "caller_over_1s",
          "caller_over_1s_s", "steps_over_1s", "steps_over_1s_wall_s",
          "steps_over_1s_wait_s", "steps_over_1s_starved_s", "steps",
          "step_wall_s", "device_wait_s")


def main(cell_name, seed, seconds, trace, recorder=False, rehearse=False):
    import jax

    from chipbench.harness import loader, runner, serving
    from chipbench.metrics import _inflight, _program

    kept, took = {}, {}
    build, drive = serving.build_engine, serving.drive

    def build_keeping(cell, model):
        engine = build(cell, model)
        if recorder:
            from paddle_tpu.observability.tracing import TraceRecorder

            engine.tracer = kept["recorder"] = TraceRecorder(
                max_events=4_000_000)
        return engine

    def drive_keeping(*a, **kw):
        kept["win"] = drive(*a, **kw)
        return kept["win"]

    def timed(name, fn):
        def call(*a, **kw):
            t = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                took[name] = time.perf_counter() - t
        return call

    serving.build_engine, serving.drive = build_keeping, drive_keeping
    jax.profiler.start_trace = timed("start_trace", jax.profiler.start_trace)
    jax.profiler.stop_trace = timed("stop_trace", jax.profiler.stop_trace)
    line = runner.run_cell(loader.load(cell_name, rehearse=rehearse),
                           seed=seed, seconds=seconds, trace=trace,
                           rehearse=rehearse, t_process=T_PROCESS)
    print("result", json.dumps(line))
    win = kept["win"]
    s0, s1 = win["stats0"], win["stats1"]
    if "device_starved_s" not in s1:
        print("ledger: the program has none")
        return
    d = {k: s1[k] - s0[k] for k in LEDGER}
    steps = max(d["steps"], 1)
    print("ledger window_s", win["window_s"], json.dumps(d))
    print("ledger a step, ms: " + " ".join(
        f"{k.removesuffix('_s')} {1e3 * d[k] / steps:.4f}" for k in LEDGER
        if k.endswith("_s")))
    window = win["window_s"] - d["caller_over_1s_s"]
    print(f"ledger floor {100 * d['device_starved_s'] / window:.4f}% "
          f"ceiling {100 * (d['device_starved_s'] + d['device_maybe_starved_s']) / window:.4f}% "
          f"of {window:.3f} s; drains {d['drains']} in {d['steps']} steps; "
          f"profiler: " + " ".join(f"{k} {v:.3f}s" for k, v in took.items()))
    if recorder:
        print("recorder events", len(kept["recorder"].events),
              "dropped", kept["recorder"].dropped)
    if trace:
        prog = _program.read(_program.trace_file(cell_name))
        _dump(prog, os.path.join(_program.ROOT, "chiprun_out",
                                 f"{cell_name}.{seed}.program.json.gz"))
        print(_inflight.describe(prog))
        print("idle gaps over 1 ms by innermost pt span: " + "; ".join(
            f"{k} {t * 1e3:.2f}ms x{c}"
            for k, t, c in _program.gaps_by_span(prog, 1e-3)))
        print("idle gaps, all, by innermost pt span: " + "; ".join(
            f"{k} {t * 1e3:.2f}ms x{c}"
            for k, t, c in _program.gaps_by_span(prog)))


def _dump(prog, path):
    """What ``_inflight.account`` reads of a trace, small enough to bring
    back: the modules, the ``pt.*`` spans and the device's busy intervals
    (``load`` makes a Program of it, each busy interval one op)."""
    import gzip

    from chipbench.harness.trace import union

    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump({"modules": prog.modules,
                   "busy": union([(o.t0, o.t1) for o in prog.ops]),
                   "spans": [(s.name, s.t0, s.t1, s.args)
                             for s in prog.spans]}, f)


def load(path):
    """The Program of a ``_dump``."""
    import gzip

    from chipbench.metrics._inflight import nest
    from chipbench.metrics._program import Op, Program, Span

    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return Program(ops=[Op("busy", "", a, b) for a, b in d["busy"]],
                   modules=[tuple(m) for m in d["modules"]],
                   spans=nest([Span(n, a, b, args)
                               for n, a, b, args in d["spans"]]))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
         bool(int(sys.argv[4])), recorder="recorder" in sys.argv[5:],
         rehearse="rehearse" in sys.argv[5:])
