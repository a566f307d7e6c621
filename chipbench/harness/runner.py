"""One run of one cell: set-up, window, check, metrics, last line."""

from __future__ import annotations

import dataclasses
import gc
import os
import statistics

from . import check, device, serving, training
from . import trace as trace_lib
from . import weights as W
from .clock import Spans, now, percentile
from .loader import ROOT


class Refused(Exception):
    """The run gives no result: something compiled inside the window, or a
    metric the cell must report could not be read."""


@dataclasses.dataclass
class Run:
    """What the per-layer readers get."""
    cell: object
    device: dict                 # platform, kind, count, peaks
    window: dict                 # what the driver's window returned
    spans: Spans
    e2e: dict                    # this run's end-to-end values by name
    trace: object = None         # trace.Reduced, or None
    trace_window_s: float = 0.0


def say(msg: str):
    print(msg, flush=True)


def run_cell(cell, *, seed: int, seconds: float, trace: bool, rehearse: bool,
             t_process: float, describe_trace: bool = False) -> dict:
    counter = device.CompileCounter()
    cache = device.place_compile_cache(rehearse)
    chips = 1 if rehearse else cell.chips
    dev = device.require(chips, rehearse)
    say(f"chipbench {cell.name} seed={seed} seconds={seconds} "
        f"trace={int(trace)} on {dev['platform']} {dev['kind']!r} x"
        f"{dev['count']} compile_cache={cache}")
    spans = Spans(annotate=trace)
    tracer = None
    if trace:
        t = cell.spec.get("trace", {})
        length = min(float(t.get("seconds", 2.0)), 0.5 * seconds)
        tracer = trace_lib.Tracer(
            os.path.join(ROOT, ".chipbench_trace", cell.name),
            float(t.get("start_share", 0.4)) * seconds, length)
    driver = cell.spec["driver"]
    if driver == "serve":
        out = _serve(cell, seed, seconds, spans, tracer, counter, t_process)
    elif driver == "train":
        out = _train(cell, seed, seconds, spans, tracer, counter, t_process)
    else:
        raise Refused(f"cell {cell.name}: unknown driver {driver!r}")
    window, compared, e2e, attempted, failed, info = out
    if window["compiles_in_window"]:
        raise Refused(f"{window['compiles_in_window']} compilation(s) "
                      f"inside the measured window: the warm-up missed a "
                      f"shape")
    for c in compared:
        say(c.line())
    for k, v in info.items():
        say(f"info {k}: {v}")
    correct = all(c.ok for c in compared)
    run = Run(cell=cell, device=dev, window=window, spans=spans, e2e=e2e)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    dev_out = {"platform": "cpu" if rehearse else dev["platform"],
               "kind": dev["kind"], "count": dev["count"],
               "memory_peak_bytes": window["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed)}
    if not trace:
        values = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    else:
        path = tracer.file()
        if tracer.state != "done" or path is None:
            raise Refused("--trace 1: no trace was written")
        red = trace_lib.reduce(path, cpu_rehearsal=rehearse)
        if describe_trace:
            say(trace_lib.describe(red))
        summ = trace_lib.summary(red, chips)
        if not summ or summ["busy_s"] <= 0:
            raise Refused("--trace 1: no operation ran on the device in "
                          "the traced window")
        run.trace, run.trace_window_s = red, tracer.window_s
        dev_out["busy_s"] = summ["busy_s"]
        dev_out["window_s"] = max(tracer.window_s, summ["span_s"])
        line["breakdown"] = {"device_ops": summ["device_ops"],
                             "idle_gaps": summ["idle_gaps"]}
        values = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(run)
            if v is None:
                say(f"info per-layer metric {m['name']}: nothing to read")
                continue
            values[m["name"]] = float(v)
    line["metrics"] = {k: {"value": v, "unit": units[k]}
                       for k, v in values.items()}
    line["device"] = dev_out
    return line


# ---- serving ----------------------------------------------------------------

def _serve(cell, seed, seconds, spans, tracer, counter, t_process):
    cfg = cell.config
    vocab = int(cfg["vocab_size"])
    sched = cell.generator.generate(cell.traffic, seed, vocab)
    with spans.span("build"):
        model = cell.adapter.build_model(
            cfg, max_positions=int(cell.spec["engine"]["max_len"]))
        cell.adapter.assign(model, W.model_weights(cell.leaf_table, seed))
        engine = serving.build_engine(cell, model)
    serving.warm_up(engine, cell, vocab, sched.eos_token_id, sched.sampling,
                    spans)
    misses_setup, split = counter.cache_misses, counter.split()
    compiles0 = counter.compiles
    say("window: starts")
    t_window = now()
    win = serving.drive(engine, sched, seconds, spans, tracer)
    win["compiles_in_window"] = counter.compiles - compiles0
    win["t0"] = t_window
    done = win["done"]
    if win["exhausted"]:
        raise Refused("the traffic file's request pool ran out inside the "
                      "window: raise 'requests'")
    e2e = {"setup_s": t_window - t_process,
           # over the span of whole engine steps inside the window: the
           # tokens seen by the last poll inside it, over the time to it
           "serve_tok_s": win["tokens"] / max(win["t_tokens"] - t_window,
                                              1e-9)}
    failed = sum(1 for lv in done if lv.req.failed)
    tpots = serving.tpots_ms(win)
    ttft = [(lv.t_first - lv.sent) * 1e3 for lv in done if lv.seen]
    info = {
        "requests": f"sent {win['sent']} completed {len(done)} failed "
                    f"{failed} steps {win['steps']} tokens {win['tokens']} "
                    f"window {win['window_s']:.3f}s",
        "engine_step_ms": (f"median {statistics.median(win['step_s']) * 1e3:.1f}"
                           f" max {max(win['step_s']) * 1e3:.1f}"
                           if win["step_s"] else "-"),
        "tpot_p95_ms(per-layer here: the batch is always full)":
            f"{percentile(tpots, 95):.1f} over {len(tpots)} requests"
            if tpots else "-",
        "ttft_p95_ms(not a metric here: queueing by construction)":
            f"{percentile(ttft, 95):.1f}" if ttft else "-",
        "setup": f"cache_misses {misses_setup} spans " + " ".join(
            f"{k}={v[1]:.1f}s" for k, v in spans.totals.items()
            if k in ("build", "warm_up")) + " jax " + split,
    }
    win["memory_peak_bytes"], info["memory"] = device.memory_peak(cell.chips)
    # the check, once the pool is gone
    del engine, model
    win["sampler"].engine = None
    gc.collect()
    t_ref = now()
    compared, facts = check.check_served(cell, seed, done, sched)
    info["reference"] = (f"{facts['checked_requests']} requests "
                         f"{facts['checked_tokens']} tokens in "
                         f"{now() - t_ref:.1f}s after the window")
    return win, compared, e2e, len(done), failed, info


# ---- training ---------------------------------------------------------------

def _train(cell, seed, seconds, spans, tracer, counter, t_process):
    cfg = cell.config
    vocab = int(cfg["vocab_size"])
    batches = cell.generator.batches(cell.traffic, seed, vocab)
    first = [next(batches), next(batches)]

    def replay():
        yield from first
        yield from batches

    with spans.span("build"):
        eng = training.build(cell, seed)
    feed = training.Feed(eng, replay(), spans)
    with spans.span("first_steps"):
        prog = training.first_steps(cell, eng, feed, seed,
                                    training.param_names(eng))
    for _ in range(int(cell.spec.get("warm_steps", 2))):
        feed.step()
    misses_setup, split = counter.cache_misses, counter.split()
    compiles0 = counter.compiles
    say("window: starts")
    t_window = now()
    win = training.drive(feed, seconds, tracer)
    win["compiles_in_window"] = counter.compiles - compiles0
    win["memory_peak_bytes"], memory = device.memory_peak(cell.chips)
    tokens = int(cell.traffic["sequences"]) * int(cell.traffic["seq_len"])
    e2e = {"setup_s": t_window - t_process,
           # over the span of the whole steps that ended inside the window
           "train_tok_s_chip": win["steps_inside"] * tokens
           / max(win["ends"][win["steps_inside"] - 1] - win["t0"], 1e-9)
           / cell.chips if win["steps_inside"] else 0.0}
    # the reference, once the program's state is gone: the first two steps
    # on the same two batches
    del eng, feed
    gc.collect()
    t_ref = now()
    ref = check.reference_training(cell, seed, first,
                                   training.hyper_of(cell))
    compared = check.compare_training(prog, ref, cell.spec["limits"])
    compared.append(check.check_losses(
        win["losses"], vocab, float(cell.spec["limits"]["loss_band"])))
    step_ms = [1e3 * (b - a) for a, b in zip([win["t0"]] + win["ends"],
                                             win["ends"])]
    info = {
        "steps": f"{win['steps_inside']} inside the window of "
                 f"{win['steps']} run; step ms median "
                 f"{statistics.median(step_ms):.2f} max {max(step_ms):.2f}",
        "losses": " ".join(f"{x:.4f}" for x in
                           prog["loss"] + win["losses"][:3]) + " ...",
        "reference": f"{now() - t_ref:.1f}s after the window, once the "
                     f"program's state was freed",
        "memory": memory,
        "setup": f"cache_misses {misses_setup} spans " + " ".join(
            f"{k}={v[1]:.1f}s" for k, v in spans.totals.items()
            if k in ("build", "first_steps")) + " jax " + split,
    }
    return win, compared, e2e, win["steps"], 0, info
