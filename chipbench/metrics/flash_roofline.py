"""Kernels: flash attention forward + dq + dkv against the compute
roofline. Time: the Pallas custom calls of the train step (the only ones
it holds) summed over the traced window, per train step. FLOPs:
chipbench/ops/flash.py at the cell's batch, sequence, heads and head size,
times the layers. Compute-bound at 4096: FLOPs over the chip's 197 TFLOP/s
is the least time."""

from chipbench.harness import trace
from chipbench.ops import flash


def read(run):
    red = run.trace
    if red is None or not red.devices:
        return None
    dev = red.devices[sorted(red.devices)[0]]
    steps = [m for m in dev["modules"] if "train_step" in m[0]]
    if not steps:
        return None
    lo, hi = min(m[1] for m in steps), max(m[2] for m in steps)
    kernel = [t1 - t0 for name, t0, t1 in trace.inside(dev["ops"], lo, hi)
              if trace.is_pallas(name)]
    if not kernel:
        return None
    cfg, job = run.cell.config, run.cell.traffic
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    flops = cfg["num_hidden_layers"] * flash.flash_flops(
        int(job["sequences"]), int(job["seq_len"]),
        cfg["num_attention_heads"], hd)["total"]
    least = flops / run.device["peaks"]["bf16_flops"]
    return 100.0 * least / (sum(kernel) / len(steps))
