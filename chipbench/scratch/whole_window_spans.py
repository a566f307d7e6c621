"""Scratch (never a run): a serving cell's whole window with a
``TraceRecorder`` on the engine, for PERF.md's question about the stalled
step: prints ``step_max_s`` / ``step_max_wait_s``, the window's steps by
wall time, and for the longest of them what each ``pt.serve.*`` child took,
so that a stall reads as device wait or as host work. The harness passes no
``tracer=``; this lays one on the engine it builds and runs the cell as
``run.py --trace 0`` does.

    python3 chipbench/scratch/whole_window_spans.py <cell> <seed> <seconds> [rehearse]
"""

import os
import statistics
import sys
import time

T_PROCESS = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(cell_name, seed, seconds, rehearse=False):
    from chipbench.harness import loader, runner, serving
    from paddle_tpu.observability.tracing import TraceRecorder

    rec = TraceRecorder(max_events=2_000_000)
    kept = {}
    build = serving.build_engine

    def build_with_recorder(cell, model):
        engine = build(cell, model)
        engine.tracer = rec
        kept["stats"] = engine.stats        # the dict, not the engine
        return engine

    serving.build_engine = build_with_recorder
    line = runner.run_cell(loader.load(cell_name, rehearse=rehearse),
                           seed=seed, seconds=seconds, trace=False,
                           rehearse=rehearse, t_process=T_PROCESS)
    print("result", line)
    stats = kept["stats"]
    print("stats", {k: stats[k] for k in (
        "steps", "step_wall_s", "device_wait_s", "decode_blocks",
        "decode_block_steps", "programs_built", "step_max_s",
        "step_max_wait_s")})
    spans = [e for e in rec.events if e["name"].startswith("pt.serve.")]
    steps = [e for e in spans if e["name"] == "pt.serve.step"]
    warm = [e for e in steps if not any(
        b["name"] == "pt.serve.build" and e["ts"] <= b["ts"] <= e["ts"]
        + e["dur"] for b in spans)]
    dur = sorted(e["dur"] * 1e-3 for e in warm)
    print(f"steps without a build: {len(warm)}; ms median "
          f"{statistics.median(dur):.1f} p95 {dur[int(0.95 * len(dur))]:.1f} "
          f"max {dur[-1]:.1f}")
    for st in sorted(warm, key=lambda e: -e["dur"])[:5]:
        kids = {}
        for e in spans:
            if (e["args"].get("parent") == "pt.serve.step"
                    and st["ts"] <= e["ts"] <= st["ts"] + st["dur"]):
                kids[e["name"]] = kids.get(e["name"], 0.0) + e["dur"] * 1e-3
        print(f"step {st['args']['step']}: {st['dur'] * 1e-3:.1f} ms  "
              + "  ".join(f"{k.removeprefix('pt.serve.')} {v:.1f}"
                          for k, v in sorted(kids.items(),
                                             key=lambda kv: -kv[1])))
    total = sum(e["dur"] for e in warm)
    covered = sum(e["dur"] for e in spans
                  if e["args"].get("parent") == "pt.serve.step"
                  and any(s["ts"] <= e["ts"] <= s["ts"] + s["dur"]
                          for s in warm[:50]))
    first = sum(e["dur"] for e in warm[:50])
    print(f"self time of pt.serve.step over the first 50 such steps: "
          f"{100 * (1 - covered / max(first, 1e-9)):.2f}% "
          f"(all steps' wall {total * 1e-6:.1f} s)")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
         rehearse=sys.argv[4:] == ["rehearse"])
