"""Kernels: the state-space layers' chunked scan against its roofline. Time:
leaf-op device time under ``pt.ssm.scan`` inside the executions of
``jit_pt_prefill_chunk`` in the traced window. Work: the chunks the traced
window ran, each row the engine packed being ``prefill_chunk / chunk_size``
chunks (the ``rows`` of the ``pt.serve.prefill`` host spans that begin
inside the trace; a pack's dummy rows count nothing although the device
multiplies them), times the Mamba-2 layers, times
``chipbench/ops/ssd.py``'s ``chunk_flops`` and ``chunk_bytes``. The least
time is the larger of FLOPs over the chip's peak and bytes over its
bandwidth."""

from chipbench.metrics import _program
from chipbench.metrics._scopes import leaves_of, under
from chipbench.ops import ssd


def read(run):
    prog = _program.of(run)
    if prog is None or not prog.ops:
        return None
    leaves = leaves_of(prog, "jit_pt_prefill_chunk")
    if leaves is None:
        return None
    seconds = sum(o.t1 - o.t0 for o in leaves if under(o, ("pt.ssm.scan",)))
    lo, hi = prog.ops[0].t0, max(o.t1 for o in prog.ops)
    rows = 0
    for s in prog.spans:
        if s.name == "pt.serve.prefill" and lo <= s.t0 < hi:
            try:
                rows += int(float(s.args.get("rows", 0)))
            except (TypeError, ValueError):
                return None
    if seconds <= 0 or rows <= 0:
        return None
    cfg, eng = run.cell.config, run.cell.spec["engine"]
    page, chunk = int(eng["page_size"]), int(cfg["chunk_size"])
    row_tokens = int((eng.get("prefix_cache") or {}).get("prefill_chunk")
                     or min(int(eng["max_len"]), 8 * page))
    row_tokens = -(-row_tokens // page) * page
    chunks = rows * max(1, row_tokens // chunk)
    layers = cfg["hybrid_override_pattern"].count("M")
    dims = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"])
    peaks = run.device["peaks"]
    least = chunks * layers * max(
        ssd.chunk_flops(*dims, min(chunk, row_tokens)) / peaks["bf16_flops"],
        ssd.chunk_bytes(*dims, min(chunk, row_tokens))
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
