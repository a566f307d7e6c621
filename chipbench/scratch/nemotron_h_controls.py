#!/usr/bin/env python3
"""The controls of ``correct`` that a state kept a SEQUENCE adds (scratch,
never a run): ``scratch/lfm2_controls.py``'s readings (one engine warmed
once, one seed's weights freed before the next are drawn) and three more
faults, each of which touches NOTHING but the ``SeqState`` pools of
``caches["kv"]`` (K and V stay sound, so the fault cannot be caught through
them) and each of which the run's own check, at the cell's committed limits
and the reference's committed ``ROUTE_MARGIN``, has to call NOT correct:

    python3 chipbench/scratch/nemotron_h_controls.py --workload <cell> \\
        --seeds 1 [--plain-seeds 2,3] [--fp8-seeds 4] \\
        [--state-rolled-seeds 5] [--state-zeroed-seeds 6] \\
        [--state-bf16-seeds 7] [--fault-seeds 8] [--seconds 40] \\
        [--out file.jsonl] [--dump dir]

``--state-rolled-seeds``: every 7 engine steps each layer's matrix state and
conv window are rolled by one SLOT (every sequence resumes from its
neighbour's state). ``--state-zeroed-seeds``: every 7 steps they are zeroed
(a state dropped, as at a chunk edge or a slot's reuse gone wrong).
``--state-bf16-seeds``: after EVERY engine step the matrix state is rounded
to bfloat16's values (a pool kept in bfloat16 would round after every token;
an engine step is a block of up to 8: the milder fault). The other kinds
are ``lfm2_controls.py``'s; the faults run last."""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import lfm2_controls as base  # noqa: E402
from chipbench import control  # noqa: E402

#: kind -> engine steps between two applications
EVERY = {"state_rolled": 7, "state_zeroed": 7, "state_bf16": 1}


def _fault(kind):
    """``caches["kv"]`` -> the same with every SeqState put through the
    fault, the K and V pairs as they are; jitted, in place."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.paged_attention import SeqState

    def one(e):
        if kind == "state_rolled":
            return SeqState(jnp.roll(e.ssm, 1, axis=0),
                            jnp.roll(e.conv, 1, axis=0))
        if kind == "state_zeroed":
            return SeqState(jnp.zeros_like(e.ssm), jnp.zeros_like(e.conv))
        return SeqState(jax.lax.reduce_precision(
            e.ssm, exponent_bits=8, mantissa_bits=7), e.conv)

    return jax.jit(lambda kv: [one(e) if isinstance(e, SeqState) else e
                               for e in kv], donate_argnums=0)


class SeqFaultBench(base.FreeingBench):
    fault = None

    def _window(self, seed, seconds, lower, every_steps):
        if self.fault is None:
            return super()._window(seed, seconds, lower, every_steps)
        eng, every, apply = self.engine, EVERY[self.fault], _fault(self.fault)
        real, count = eng.step, [0, 0]

        def step():
            count[0] += 1
            if count[0] % every == 0:
                eng.caches = dict(eng.caches, kv=apply(eng.caches["kv"]))
                count[1] += 1
            return real()

        eng.step = step
        try:
            return super()._window(seed, seconds, lower, 0)
        finally:
            del eng.step
            control.say(f"{self.fault} seed={seed}: applied {count[1]} times "
                        f"in {count[0]} engine steps")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    for flag in ("seeds", "plain-seeds", "fp8-seeds", "state-rolled-seeds",
                 "state-zeroed-seeds", "state-bf16-seeds", "fault-seeds"):
        ap.add_argument("--" + flag, default="")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--dump", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from chipbench.harness import device, loader

    cell = loader.load(args.workload, rehearse=args.rehearse)
    device.require(1, args.rehearse)
    device.place_compile_cache(args.rehearse)
    plan = [(x, kind) for kind, text in (
        ("sound", args.seeds), ("sound_plain", args.plain_seeds),
        ("fp8_program", args.fp8_seeds),
        ("state_zeroed", args.state_zeroed_seeds),
        ("state_rolled", args.state_rolled_seeds),
        ("rolled_pool", args.fault_seeds),
        ("state_bf16", args.state_bf16_seeds)) for x in control._seeds(text)]
    if not plan:
        raise SystemExit("no seeds")
    bench = SeqFaultBench(cell, plan[0][0])
    bad = 0
    for seed, kind in plan:
        bench.fault = kind if kind in EVERY else None
        try:
            row = control.serving_seed(bench, seed, args.seconds, kind)
        except Exception as e:      # one seed lost, not the whole process
            control.say(f"{kind} seed={seed}: no reading: "
                        f"{type(e).__name__}: {e}")
            bad += 1
            continue
        if args.dump:
            base.dump_sample(cell, seed, kind, bench.last[0]["done"],
                             args.dump)
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
