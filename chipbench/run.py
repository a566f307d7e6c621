#!/usr/bin/env python3
"""chipbench: one process, one cell, once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Enables the compile cache, builds the cell's model on the device from the
seed, warms every shape the cell uses, measures for ``--seconds``, checks
the timed path against the plain reference, prints each number compared
beside its limit and, as the last line, the contract's one JSON object.
Exits non-zero and prints no last line without the TPUs the cell asks for,
on an unknown device kind, or when anything compiled inside the window.

``--rehearse`` runs the same control flow on the CPU at the tiny preset in
``chipbench/rehearse/``, names the device ``cpu`` and exits 3 after
printing, so that nothing can take it for a result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe-trace", action="store_true",
                    help="with --trace 1: print what the trace holds")
    args = ap.parse_args(argv)

    from chipbench.harness import loader, runner
    from chipbench.harness.device import NoChip

    try:
        cell = loader.load(args.workload, rehearse=args.rehearse)
        line = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                               trace=bool(args.trace),
                               rehearse=args.rehearse, t_process=T_PROCESS,
                               describe_trace=args.describe_trace)
    except (loader.CellError, NoChip, runner.Refused) as e:
        print(f"chipbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
