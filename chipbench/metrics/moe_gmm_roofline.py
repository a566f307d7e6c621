"""Kernels: the experts' matmuls of the decode block against their memory
roofline. Time: leaf-op device time under ``pt.moe.experts`` (the kernel
``pt_grouped_matmul`` where the dispatch is the kernel, else whatever the
chosen dispatch runs there) inside the executions of ``jit_pt_decode_block``
in the traced window. Token steps of those executions: the sampler's ``sort``
runs once a step (the cell's batches always sample). Bytes:
chipbench/ops/grouped_matmul.py for one routed layer's three matmuls over the
experts VISITED (the window's ``moe_experts_touched`` / ``moe_layer_steps``,
not all of them: an expert without a row need not be read), times the expert
layers and those token steps. Memory-bound: bytes over the chip's peak
bandwidth is the least time."""

from chipbench.metrics import _program
from chipbench.metrics._scopes import (counter_delta, leaves_of, token_steps,
                                       under)
from chipbench.ops import grouped_matmul


def read(run):
    got = counter_delta(run, "moe_experts_touched", "moe_layer_steps",
                        "moe_rows_routed")
    prog = _program.of(run)
    if got is None or prog is None or got[1] <= 0:
        return None
    leaves = leaves_of(prog, "jit_pt_decode_block")
    if leaves is None:
        return None
    touched, layer_steps, routed = got
    seconds = sum(o.t1 - o.t0 for o in leaves if under(o, ("pt.moe.experts",)))
    steps = token_steps(leaves)
    if seconds <= 0 or not steps:
        return None
    cfg = run.cell.config
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    need = steps * layers * grouped_matmul.expert_ffn_bytes(
        routed / layer_steps, touched / layer_steps, cfg["hidden_size"],
        cfg["moe_intermediate_size"])
    return 100.0 * need / run.device["peaks"]["hbm_bytes_per_s"] / seconds
