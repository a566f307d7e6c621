#!/usr/bin/env python3
"""The controls of ``correct``: what the limits are read from, and runs
that have to come out as NOT correct.

Not part of a benchmark run; the driver never calls it. On the chip at the
cell's own size it reads, over a dozen seeds in ONE process (a serving
cell's set-up is minutes; its weights are swapped under the live engine):

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        [--fp8-seeds 4,5,6] [--fault-seeds 7] [--seconds 50] [--out file.jsonl]

Training cell, each of ``--seeds``: the reference with its weights rounded
to float8_e4m3fn, put in the program's place, against the plain reference
(no window needed).

Serving cell, each of ``--seeds``: a window at the cell's own load with the
sound program, then on the same sample of served requests (a) the sound
readings of every number compared, (b) the readings of the reference in the
program's place, teacher-forced at the same positions: with fp8 weights (its
first token where the request is greedy, a token it draws where sampled),
sound but drawing with no top_p cut, and sound but drawing at temperature
1.0. Each of ``--fp8-seeds``: the PROGRAM itself with fp8-rounded weights
handed to it; the run's own check has to say not correct. Each of
``--fault-seeds``: the sound program with its KV pool rolled by one page
every few steps (every block table then points at its neighbour's page);
not correct.

One line per seed and number; exit 0 when every control failed the check as
it must, 1 when one passed.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUMBERS = ("served_token_logit_gap", "sampled_token_nucleus_gap",
           "sampled_mass_above_off")


def say(msg: str):
    print(msg, flush=True)


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def train_control(cell, seed):
    from chipbench.harness import check, training

    batches = cell.generator.batches(cell.traffic, seed,
                                     int(cell.config["vocab_size"]))
    first = [next(batches), next(batches)]
    hyper = training.hyper_of(cell)
    ref = check.reference_training(cell, seed, first, hyper)
    low = check.reference_training(cell, seed, first, hyper,
                                   lower=check.round_fp8)
    compared = check.compare_training(low, ref, cell.spec["limits"])
    for c in compared:
        say(f"control seed={seed} {c.line()}")
    return {"seed": seed, "kind": "fp8_reference", "passed": all(
        c.ok for c in compared), **{c.name: c.value for c in compared}}


class ServingBench:
    """One engine, warmed once; the weights of each seed are put under it."""

    def __init__(self, cell, first_seed):
        from chipbench.harness import serving
        from chipbench.harness import weights as W
        from chipbench.harness.clock import Spans

        self.cell, self.spans = cell, Spans()
        cfg = cell.config
        self.vocab = int(cfg["vocab_size"])
        self.model = cell.adapter.build_model(
            cfg, max_positions=int(cell.spec["engine"]["max_len"]))
        cell.adapter.assign(self.model,
                            W.model_weights(cell.leaf_table, first_seed))
        self.engine = serving.build_engine(cell, self.model)
        sched = cell.generator.generate(cell.traffic, first_seed, self.vocab)
        serving.warm_up(self.engine, cell, self.vocab, sched.eos_token_id,
                        sched.sampling, self.spans)

    def window(self, seed, seconds, lower=None, every_steps=0):
        """Empty the engine, put ``seed``'s weights under it (through
        ``lower`` for the fp8 program), drive one window; with
        ``every_steps`` the KV pool is rolled by one page that often."""
        import jax
        import jax.numpy as jnp

        from chipbench.harness import serving
        from chipbench.harness import weights as W

        eng = self.engine
        for req in list(getattr(eng, "_queue", [])):
            eng.withdraw_queued(req.rid)
        for req in list(eng._occupied.values()):
            eng.withdraw_active(req.rid)
        eng.finished()
        if eng.has_work():
            raise RuntimeError("control: the engine did not come out empty")
        w = W.model_weights(self.cell.leaf_table, seed)
        if lower is not None:
            w = jax.jit(lambda t: jax.tree_util.tree_map(lower, t),
                        donate_argnums=0)(w)
        self.cell.adapter.assign(self.model, w)
        eng._params = [t._data for t in eng._tensors]
        del w
        sched = self.cell.generator.generate(self.cell.traffic, seed,
                                             self.vocab)
        real = eng.step
        if every_steps:
            count = [0]
            roll = jax.jit(lambda kv: jax.tree_util.tree_map(
                lambda a: jnp.roll(a, 1, axis=0), kv), donate_argnums=0)

            def step():
                count[0] += 1
                if count[0] % every_steps == 0:
                    eng.caches = dict(eng.caches, kv=roll(eng.caches["kv"]))
                return real()

            eng.step = step
        try:
            win = serving.drive(eng, sched, seconds, self.spans)
        finally:
            if every_steps:
                del eng.step
        gc.collect()
        return win, sched


def serving_seed(bench, seed, seconds, kind):
    """One window and its readings; ``kind`` is sound, fp8_program or
    rolled_pool."""
    import statistics

    from chipbench.harness import check, device
    from chipbench.harness.clock import now

    cell = bench.cell
    win, sched = bench.window(
        seed, seconds, lower=check.round_fp8 if kind == "fp8_program" else None,
        every_steps=7 if kind == "rolled_pool" else 0)
    t0 = now()
    compared, facts = check.check_served(cell, seed, win["done"], sched)
    for c in compared:
        say(f"{kind} seed={seed} {c.line()}")
    row = {"seed": seed, "kind": kind,
           "passed": all(c.ok for c in compared),
           "completed": len(win["done"]), "tokens": win["tokens"],
           "step_ms_median": 1e3 * statistics.median(win["step_s"]),
           "step_ms_max": 1e3 * max(win["step_s"]), **facts,
           **{c.name: c.value for c in compared}}
    if kind == "sound":
        sample = check.pick_sample(win["done"], seed, cell.spec["check"])
        s = sched.sampling
        for name, lower, draw in (
                ("fp8_reference", check.round_fp8, s),
                ("no_cut_reference", None, dict(s, top_p=1.0)),
                ("hot_reference", None, dict(s, temperature=1.0))):
            got = check.served_numbers(check.served_stats(
                cell, seed, sample, s, lower=lower, draw=draw))
            row[name] = {k: got[k] for k in NUMBERS}
            say(f"{name} seed={seed} " + " ".join(
                f"{k}={got[k]:.6g}" for k in NUMBERS))
    row["reference_s"] = now() - t0
    row["memory"] = device.memory_peak(1)[1]
    say(f"{kind} seed={seed}: {row['completed']} completed, step ms median "
        f"{row['step_ms_median']:.1f} max {row['step_ms_max']:.1f}, "
        f"reference {row['reference_s']:.1f}s; the check says "
        f"{'correct' if row['passed'] else 'NOT correct'}")
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--fp8-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench.harness import device, loader

    cell = loader.load(args.workload, rehearse=args.rehearse)
    device.require(1, args.rehearse)
    device.place_compile_cache(args.rehearse)
    plan = ([(s, "sound") for s in _seeds(args.seeds)]
            + [(s, "fp8_program") for s in _seeds(args.fp8_seeds)]
            + [(s, "rolled_pool") for s in _seeds(args.fault_seeds)])
    if not plan:
        raise SystemExit("no seeds")
    serve = cell.spec["driver"] == "serve"
    if not serve and len(plan) != len(_seeds(args.seeds)):
        raise SystemExit("a training cell has the fp8 reference control "
                         "only: give --seeds")
    bench = ServingBench(cell, plan[0][0]) if serve else None
    bad = 0
    for seed, kind in plan:
        try:
            row = (serving_seed(bench, seed, args.seconds, kind) if serve
                   else train_control(cell, seed))
        except Exception as e:      # one seed lost, not the whole process
            say(f"{kind} seed={seed}: no reading: {type(e).__name__}: {e}")
            bad += 1
            if (seed, kind) == plan[0]:
                raise               # a fault of the tool: stop here
            continue
        # a sound run has to pass, everything else has to fail
        wrong = row["passed"] != (row["kind"] == "sound")
        bad += wrong
        if wrong:
            say(f"{row['kind']} seed={seed}: "
                + ("THE SOUND PROGRAM FAILED THE CHECK" if row["passed"] is
                   False else "THE CONTROL PASSED: a limit is too loose"))
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
