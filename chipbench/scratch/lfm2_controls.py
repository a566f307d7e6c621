#!/usr/bin/env python3
"""The controls of ``correct`` for a cell whose weights fill most of the chip
(scratch, never a run): ``chipbench/control.py``'s readings, one engine warmed
once, but one seed's weights are FREED before the next are drawn (control.py
draws the next while the last still sit under the engine, and checks against
the reference with them there: neither fits beside 10 GB), and one more fault,
the one a model with state layers adds:

    python3 chipbench/scratch/lfm2_controls.py --workload <cell> \\
        --seeds 1,2,3 [--plain-seeds 7,8] [--fp8-seeds 4] \\
        [--zero-state-seeds 5] [--fault-seeds 6] [--seconds 50] \\
        [--out file.jsonl] [--dump dir]

``--seeds``: sound windows, each with the reference-in-the-program's-place
controls (fp8 weights, no top_p cut, temperature 1.0) on its own sample;
``--plain-seeds``: sound windows without them (half the time after a window).
``--fp8-seeds``: the program with fp8-rounded weights. ``--zero-state-seeds``:
the sound program, but every admission that maps cached pages finds the state
rings of the last of them zeroed (a prefix hit that resumes the conv layers
from nothing). ``--fault-seeds``: the pool rolled by a page every 7 steps,
rings and all. Faults run last: they leave the cache in ruins. ``--dump``:
each window's checked sample (prompts, served tokens, kind) as JSON, one file
a seed; ``--replay`` reads such files back and prints the check's numbers for
them with the reference-in-the-program's-place controls, engine or no engine
(on the CPU too: the reference is the same arithmetic), so that a limit or
``ROUTE_MARGIN`` can be read again without a window. Exit 0 when every sound
seed passed and every control failed the check."""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import control


class FreeingBench(control.ServingBench):
    zero_state = False

    def _free_weights(self):
        """Shape and type stay, the arrays go: before the next seed's are
        drawn, and before the check's reference takes the chip."""
        import jax

        for t in self.engine._tensors:
            t._data = jax.ShapeDtypeStruct(t._data.shape, t._data.dtype)
        self.engine._params = None
        gc.collect()

    def window(self, seed, seconds, lower=None, every_steps=0):
        self.last = self._window(seed, seconds, lower, every_steps)
        return self.last

    def _window(self, seed, seconds, lower, every_steps):
        import jax

        eng = self.engine
        self._free_weights()
        real = eng._try_admit_prefix
        if self.zero_state:
            from paddle_tpu.ops.paged_attention import PageState

            zero = jax.jit(lambda kv, page: [
                PageState(e.ring.at[page].set(0), e.page)
                if isinstance(e, PageState) else e for e in kv],
                donate_argnums=0)

            def admit(slot, req, cow_wave=None):
                ok = real(slot, req, cow_wave)
                hit = eng._prefill_next.get(slot, 0) if ok else 0
                if hit:
                    last = eng._slot_blocks[slot][hit // eng.page_size - 1]
                    eng.caches = dict(eng.caches,
                                      kv=zero(eng.caches["kv"], last))
                    self.zeroed += 1
                return ok

            self.zeroed = 0
            eng._try_admit_prefix = admit
        try:
            return super().window(seed, seconds, lower, every_steps)
        finally:
            self._free_weights()    # the reference needs the room
            if self.zero_state:
                del eng._try_admit_prefix
                control.say(f"zeroed_state seed={seed}: the rings of "
                            f"{self.zeroed} hits' last pages were zeroed")


def dump_sample(cell, seed, kind, done, directory):
    from chipbench.harness import check

    sample = check.pick_sample(done, seed, cell.spec["check"])
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, f"{kind}_{seed}.json"), "w") as f:
        json.dump({"seed": seed, "kind": kind, "requests": [
            {"index": int(lv.plan.index), "greedy": bool(lv.plan.greedy),
             "prompt": [int(t) for t in lv.plan.prompt],
             "output": [int(t) for t in lv.req.output]}
            for lv in sample]}, f)


def replay(cell, path, sampling, controls=True):
    """The check's three numbers for a dumped sample, and (sound samples)
    the reference-in-the-program's-place controls on it."""
    import types

    import numpy as np

    from chipbench.harness import check

    with open(path) as f:
        got = json.load(f)
    sample = [types.SimpleNamespace(
        plan=types.SimpleNamespace(index=r["index"], greedy=r["greedy"],
                                   prompt=np.asarray(r["prompt"], np.int32)),
        req=types.SimpleNamespace(output=r["output"]))
        for r in got["requests"]]
    seed, kind = got["seed"], got["kind"]
    rows = [(kind, None, None)]
    if controls and kind.startswith("sound"):
        rows += [("fp8_reference", check.round_fp8, sampling),
                 ("no_cut_reference", None, dict(sampling, top_p=1.0)),
                 ("hot_reference", None, dict(sampling, temperature=1.0))]
    for name, lower, draw in rows:
        nums = check.served_numbers(check.served_stats(
            cell, seed, sample, sampling, lower=lower, draw=draw))
        control.say(f"replay {name} seed={seed} " + " ".join(
            f"{k}={nums[k]:.6g}" for k in control.NUMBERS)
            + f" greedy_tokens={nums['greedy_tokens']}"
            f" sampled_tokens={nums['sampled_tokens']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--plain-seeds", default="")
    ap.add_argument("--fp8-seeds", default="")
    ap.add_argument("--zero-state-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--dump", default="")
    ap.add_argument("--replay", nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench.harness import device, loader

    cell = loader.load(args.workload, rehearse=args.rehearse)
    if args.replay:
        for path in args.replay:
            replay(cell, path, cell.traffic["sampling"])
        return 0
    device.require(1, args.rehearse)
    device.place_compile_cache(args.rehearse)
    s = control._seeds
    plan = ([(x, "sound") for x in s(args.seeds)]
            + [(x, "sound_plain") for x in s(args.plain_seeds)]
            + [(x, "fp8_program") for x in s(args.fp8_seeds)]
            + [(x, "zeroed_state") for x in s(args.zero_state_seeds)]
            + [(x, "rolled_pool") for x in s(args.fault_seeds)])
    if not plan:
        raise SystemExit("no seeds")
    bench = FreeingBench(cell, plan[0][0])
    bad = 0
    for seed, kind in plan:
        bench.zero_state = kind == "zeroed_state"
        try:
            row = control.serving_seed(bench, seed, args.seconds, kind)
        except Exception as e:      # one seed lost, not the whole process
            control.say(f"{kind} seed={seed}: no reading: "
                        f"{type(e).__name__}: {e}")
            bad += 1
            continue
        if args.dump:
            dump_sample(cell, seed, kind, bench.last[0]["done"], args.dump)
        wrong = row["passed"] != kind.startswith("sound")
        bad += wrong
        if wrong:
            control.say(f"{kind} seed={seed}: " + (
                "THE SOUND PROGRAM FAILED THE CHECK"
                if kind.startswith("sound")
                else "THE CONTROL PASSED THE CHECK"))
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
