#!/usr/bin/env python3
"""The controls of ``correct`` for a cell of the afmoe family, and its
seeds' spread (scratch, never a run; ``chipbench/control.py``'s engine and
windows): sound windows, faults that must each come out NOT correct, and
windows that are only timed, in ONE process on the chip at the cell's own
size. Every window runs first; the references run after the engine has
gone, with the chip to themselves.

    python3 chipbench/scratch/afmoe_controls.py <workload> [--seeds 1,2]
        [--fp8 3] [--early 4] [--nomask 5] [--rope 6] [--ref-nomask 1]
        [--ref-rope 1] [--ref-nocut 1] [--ref-hot 1]
        [--timed LABEL=7,8,9[@TRAFFIC.json]] [--seconds 50]
        [--check '{"greedy": {"requests": 4}}']
        [--out chiprun_out/afmoe_controls.jsonl]

  --fp8 S     the PROGRAM with its weights rounded to float8_e4m3fn
  --early S   window pages given back one page too soon
              (``PageGroups.release_behind`` asked as if the next query
              sat a page further on: the host lets go of a page whose last
              positions that query still reads) and written over at once,
              as their next owner would within seconds at this pool's
              turnover: noise goes into those pages of every window
              layer's pool after the step
  --nomask S  the window mask dropped on the sliding layers
              (``AfmoeAttention.window = None``, rotary kept): they read
              their whole table, the stale entries behind the window
              included
  --rope S    rotary applied on the full layers too
              (``AfmoeAttention.rotary = True``)
  --ref-nomask S, --ref-rope S
              the same two faults in the REFERENCE, judging the sound
              program's window of seed S (the window of ``--seeds S`` where
              that is given too): the program side of them compiles every
              program again, seven minutes a variant at the cell's size
  --ref-nocut S, --ref-hot S
              the sound reference in the program's place on that window's
              sample, drawing with no top_p cut, or at temperature 1.0
              (``control.py``'s no_cut_reference and hot_reference)
  --timed     windows without a check, with TRAFFIC.json laid over the
              cell's mix: each window's ``serve_tok_s`` as the runner takes
              it, and the label's median and spread (IQR over median)

One line a window and number; exit 0 when every sound window passed and
every fault failed the check.
"""

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import control  # noqa: E402

FAULTS = ("fp8", "early", "nomask", "rope", "ref-nomask", "ref-rope",
          "ref-nocut", "ref-hot")
NUMBERS = control.NUMBERS


def say(msg):
    print(msg, flush=True)


class FreeingBench(control.ServingBench):
    """``control.ServingBench`` for weights that fill the chip: a seed's
    weights go before the next seed's are drawn and before the check's
    reference takes the chip (shape and type stay on the parameters)."""

    def free_weights(self):
        import gc

        import jax

        for t in self.engine._tensors:
            t._data = jax.ShapeDtypeStruct(t._data.shape, t._data.dtype)
        self.engine._params = None
        gc.collect()

    def put_weights(self, seed):
        from chipbench.harness import weights as W

        self.free_weights()
        self.cell.adapter.assign(self.model,
                                 W.model_weights(self.cell.leaf_table, seed))
        self.engine._params = [t._data for t in self.engine._tensors]

    def window(self, seed, seconds, lower=None, every_steps=0):
        self.free_weights()
        try:
            return super().window(seed, seconds, lower, every_steps)
        finally:
            self.free_weights()


def programs(bench, sched, variant, seed):
    """Put the engine's attention layers in ``variant`` ("sound", "nomask",
    "rope") and, where that changes them, drop its compiled step programs
    and warm every shape again (minutes: one build a variant, none where
    the plan keeps windows of one variant together)."""
    from chipbench.harness import serving

    eng = bench.engine
    if getattr(bench, "variant", "sound") == variant:
        return
    sliding = eng.model.config.sliding_window
    for layer in eng.model.model.layers:
        attn = layer.self_attn
        is_sliding = attn.scope.endswith("window")
        attn.window = (sliding if is_sliding and variant != "nomask"
                       else None)
        attn.rotary = is_sliding or variant == "rope"
    bench.variant = variant
    bench.put_weights(seed)                 # the warm-up serves requests
    eng._jit_mega = None
    eng._jit_chunk.clear()
    eng._jit_first.clear()
    serving.warm_up(eng, bench.cell, bench.vocab, sched.eos_token_id,
                    sched.sampling, bench.spans)


def released_early(eng, width=64):
    """Make the engine give window pages back one page too soon and write
    noise over them after the step that did; returns what undoes it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    groups = eng._groups
    right = groups.release_behind
    gone = []

    def early(slot, pos):
        before = [dict(h[slot]) for h in groups._held]
        n = right(slot, pos + groups.page)      # as if a page further on
        for gi, (g, was) in enumerate(zip(groups.windowed, before)):
            first = max(0, int(pos) - g.window + 1) // groups.page
            gone.extend(b for i, b in was.items()
                        if i >= first and i not in groups._held[gi][slot])
        return n

    window_layers = [i for i, g in enumerate(eng._layer_groups) if g]

    # donated: a copy of every layer's pool beside the pools does not fit
    @functools.partial(jax.jit, donate_argnums=0)
    def scribble(kv, idx, key):
        kv = list(kv)
        for i in window_layers:
            k, v = kv[i]
            noise = jax.random.normal(key, (idx.shape[0],) + k.shape[1:],
                                      jnp.float32).astype(k.dtype)
            kv[i] = (k.at[idx].set(noise), v.at[idx].set(noise))
        return kv

    step, count = eng.step, [0]

    def stepped():
        out = step()
        while gone:
            idx = np.full(width, groups.windowed[0].park, np.int32)
            take = [gone.pop() for _ in range(min(width, len(gone)))]
            idx[:len(take)] = take
            count[0] += len(take)
            eng.caches = dict(eng.caches, kv=scribble(
                eng.caches["kv"], jnp.asarray(idx),
                jax.random.PRNGKey(count[0])))
        return out

    groups.release_behind = early
    eng.step = stepped

    def undo():
        del groups.release_behind
        del eng.step
        say(f"early: {count[0]} pages given back a page early and written "
            f"over")

    return undo


def reference_with(cell, fault):
    """Put ``fault`` into the REFERENCE ("nomask": a sliding layer sees its
    whole history, rotary kept; "rope": rotary on the full layers too);
    returns what undoes it. The sound program's served tokens judged by
    such a reference are as far from it as a program with the fault is from
    the sound reference, and no program is compiled again for it."""
    ref = cell.reference
    right = ref._arch

    def arch(cfg, i):
        a = right(cfg, i)
        if fault == "nomask" and a["window"] is not None:
            a["window"] = 1 << 30
        if fault == "rope":
            a["rotary"] = True
        return a

    ref._arch = arch
    return lambda: setattr(ref, "_arch", right)


def window_of(bench, seed, seconds, kind):
    """One window of the engine in ``kind``; its facts without the check."""
    import statistics

    from chipbench.harness import check, serving
    from chipbench.harness.clock import now

    cell, eng = bench.cell, bench.engine
    sched = cell.generator.generate(cell.traffic, seed, bench.vocab)
    programs(bench, sched, kind if kind in ("nomask", "rope") else "sound",
             seed)
    undo = released_early(eng) if kind == "early" else None
    drive, called = serving.drive, []

    def stamped(*a, **kw):
        called.append(now())
        return drive(*a, **kw)

    serving.drive = stamped
    try:
        stats0 = dict(eng.stats)
        win, sched = bench.window(
            seed, seconds, lower=check.round_fp8 if kind == "fp8" else None)
    finally:
        serving.drive = drive
        if undo is not None:
            undo()
    s1 = win["stats1"]
    row = {"seed": seed, "kind": kind, "completed": len(win["done"]),
           "tokens": win["tokens"], "steps": win["steps"],
           # serve_tok_s as harness/runner.py takes it
           "tok_s": win["tokens"] / max(win["t_tokens"] - called[0], 1e-9),
           "step_ms_median": 1e3 * statistics.median(win["step_s"]),
           "step_ms_max": 1e3 * max(win["step_s"]),
           "window_pages_released": s1.get("window_pages_released", 0)
           - stats0.get("window_pages_released", 0),
           "prefix_hits_shortened": s1.get("prefix_hits_shortened", 0)
           - stats0.get("prefix_hits_shortened", 0)}
    say(f"{kind} seed={seed}: {row['completed']} completed, "
        f"{row['tokens']} tokens in {win['window_s']:.3f} s and "
        f"{row['steps']} steps, {row['tok_s']:.2f} tok/s, step ms median "
        f"{row['step_ms_median']:.1f} max {row['step_ms_max']:.1f}")
    return row, win["done"], sched


def remembered(ref):
    """``ref.hidden_states_many`` keeping its last answer: the checks of one
    window's sample by one reference (the served tokens, then its own
    draws) are one pass over the sample, not one each."""
    real, last = ref.hidden_states_many, {}

    def many(cfg, ids, layer_fn, top):
        key = (ref._arch, tuple(x.tobytes() for x in ids))
        if key not in last:
            last.clear()
            last[key] = real(cfg, ids, layer_fn, top)
        return last[key]

    ref.hidden_states_many = many


def judge(cell, row, done, sched, fault, spec):
    """The check of one window's sample; the numbers join a copy of
    ``row``. ``fault`` None: the reference as it stands. "nomask" / "rope":
    the fault in the reference. "nocut" / "hot": the sound reference in the
    program's place, as ``control.py`` puts it there: judged are not the
    served tokens but its own draws at the same positions with no ``top_p``
    cut, or at temperature 1.0."""
    from chipbench.harness import check, device
    from chipbench.harness.clock import now

    seed, kind = row["seed"], row["kind"] + ("+ref-" + fault if fault else "")
    cell.spec = dict(cell.spec, check=spec)
    s = sched.sampling
    draw = {"nocut": dict(s, top_p=1.0), "hot": dict(s, temperature=1.0)
            }.get(fault)
    undo = reference_with(cell, fault) if fault and not draw else None
    sample = check.pick_sample(done, seed, spec)
    t0 = now()
    try:
        if draw:
            got = check.served_numbers(check.served_stats(
                cell, seed, sample, s, draw=draw))
            compared = [check.Compared(n, got[n], float(
                cell.spec["limits"][n])) for n in NUMBERS]
            facts = {"checked_requests": len(sample), "checked_tokens":
                     got["greedy_tokens"] + got["sampled_tokens"]}
        else:
            compared, facts = check.check_served(cell, seed, done, sched)
    finally:
        if undo is not None:
            undo()
    for c in compared:
        say(f"{kind} seed={seed} {c.line()}")
    row = dict(row, kind=kind, passed=all(c.ok for c in compared),
               deepest_checked_position=max(
                   (len(lv.plan.prompt) + len(lv.req.output)
                    for lv in sample), default=0),
               **facts, **{c.name: c.value for c in compared},
               reference_s=now() - t0, memory=device.memory_peak(1)[1])
    say(f"{kind} seed={seed}: deepest checked position "
        f"{row['deepest_checked_position']}, reference "
        f"{row['reference_s']:.1f}s; the check says "
        f"{'correct' if row['passed'] else 'NOT correct'}")
    return row


def spread(values):
    import statistics

    q = statistics.quantiles(values, n=4)
    return statistics.median(values), (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", default="")
    for fault in FAULTS:
        ap.add_argument("--" + fault, default="")
    ap.add_argument("--timed", action="append", default=[],
                    metavar="LABEL=SEEDS[@TRAFFIC.json]",
                    help="windows that are only timed, with a traffic file "
                         "laid over the cell's: the label's median and spread")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--check", default="",
                    help="JSON laid over the cell's `check` (a smaller sample)")
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import gc

    from chipbench.harness import device, loader

    cell = loader.load(args.workload, rehearse=args.rehearse)
    device.require(1, args.rehearse)
    device.place_compile_cache(args.rehearse)
    spec = loader.merge(cell.spec["check"], json.loads(args.check or "{}"))
    plan = [(s, "sound") for s in control._seeds(args.seeds)]
    for fault in FAULTS:
        plan += [(s, fault) for s in control._seeds(
            getattr(args, fault.replace("-", "_")))]
    timed = []
    for item in args.timed:
        label, _, rest = item.partition("=")
        seeds, _, path = rest.partition("@")
        over = json.load(open(path)) if path else {}
        timed += [(label, over, s) for s in control._seeds(seeds)]
    if not plan and not timed:
        raise SystemExit("no seeds")
    bench = FreeingBench(cell, (plan or [(timed[0][2],)])[0][0])

    def keep(row):
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    # every window first, every reference after the engine has gone
    traffic, rates, bad = cell.traffic, {}, 0
    for label, over, seed in timed:
        cell.traffic = loader.merge(traffic, over)
        cell.generator = loader._module("generators", cell.traffic["kind"],
                                        "traffic kind")
        try:
            row, _, _ = window_of(bench, seed, args.seconds, "sound")
        except Exception as e:
            say(f"timed {label} seed={seed}: no reading: "
                f"{type(e).__name__}: {e}")
            continue
        rates.setdefault(label, []).append(row["tok_s"])
        keep(dict(row, kind="timed", label=label))
    for label, got in rates.items():
        if len(got) > 1:
            med, iqr = spread(got)
            say(f"timed {label}: {len(got)} windows, median {med:.2f} tok/s, "
                f"spread {100 * iqr:.2f}% (IQR over median), "
                + " ".join(f"{v:.2f}" for v in got))
    cell.traffic = traffic
    cell.generator = loader._module("generators", traffic["kind"], "traffic")
    windows = {}
    for seed, kind in plan:
        at = (seed, "sound" if kind.startswith("ref-") else kind)
        if at in windows:
            continue
        try:
            windows[at] = window_of(bench, seed, args.seconds, at[1])
        except Exception as e:      # one window lost, not the whole process
            say(f"{at[1]} seed={seed}: no reading: {type(e).__name__}: {e}")
    bench.free_weights()
    del bench.engine, bench.model
    gc.collect()
    remembered(cell.reference)
    # one reference's checks of one window side by side: one pass for them
    arch = lambda kind: kind in ("ref-nomask", "ref-rope") and kind
    for seed, kind in sorted(plan, key=lambda p: (p[0], str(arch(p[1])))):
        ref_side = kind.startswith("ref-")
        at = (seed, "sound" if ref_side else kind)
        if at not in windows:
            bad += 1
            continue
        try:
            row = judge(cell, *windows[at], kind[4:] if ref_side else None,
                        spec)
        except Exception as e:
            say(f"{kind} seed={seed}: no reading: {type(e).__name__}: {e}")
            bad += 1
            continue
        wrong = row["passed"] != (kind == "sound")
        bad += wrong
        if wrong:
            say(f"{kind} seed={seed}: "
                + ("THE CONTROL PASSED: a limit is too loose" if
                   row["passed"] else "THE SOUND PROGRAM FAILED THE CHECK"))
        keep(row)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
