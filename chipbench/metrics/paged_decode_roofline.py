"""Kernels: the paged decode kernel against its memory roofline. Time: the
Pallas custom calls (the only ones a decode program holds) inside the
traced decode-only steps. Bytes: chipbench/ops/paged_decode.py for every
row's context at every token step of the block, times the layers (one call
a layer a token step). Memory-bound: bytes over the chip's 819 GB/s is the
least time."""

from chipbench.harness import trace
from chipbench.metrics._decode import pure_decode_steps
from chipbench.ops import paged_decode


def read(run):
    steps = pure_decode_steps(run)
    if not steps:
        return None
    cfg = run.cell.config
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    page = int(run.cell.spec["engine"]["page_size"])
    seconds = need = 0.0
    for n, grown, ops, _ in steps:
        kernel = [t1 - t0 for name, t0, t1 in ops if trace.is_pallas(name)]
        if not kernel:
            continue
        seconds += sum(kernel)
        for j in range(n):
            lens = [ctx + j + 1 for ctx, k in grown if k > j]
            need += cfg["num_hidden_layers"] * paged_decode.paged_decode_bytes(
                lens, cfg["num_key_value_heads"], cfg["num_attention_heads"],
                hd, page)
    if seconds <= 0:
        return None
    least = need / run.device["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
