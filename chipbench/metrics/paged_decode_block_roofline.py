"""Kernels: the paged decode kernel against its memory roofline in a model
where only SOME layers are attention (``hybrid_override_pattern``: a ``*`` a
layer), read inside decode blocks of any mix of rows. Time: the Pallas
custom calls named ``pt_paged_decode`` inside the executions of
``jit_pt_decode_block`` in the traced window. Bytes:
``chipbench/ops/paged_decode.py`` for every row's context at every token
step of the traced engine steps (the driver's ``steps_log``: a row that
grew ``k`` tokens was read at ``k`` steps, its context one longer each),
times the attention layers: one call a layer a token step. Memory-bound:
bytes over the chip's peak bandwidth is the least time.
(``paged_decode_roofline`` takes every layer for an attention layer and
reads only engine steps that ran nothing but a decode block.)"""

from chipbench.harness import trace
from chipbench.metrics import _program
from chipbench.metrics._scopes import leaves_of
from chipbench.ops import paged_decode


def read(run):
    prog, log = _program.of(run), run.window.get("steps_log")
    cfg = run.cell.config
    layers = str(cfg.get("hybrid_override_pattern", "")).count("*")
    if prog is None or not log or not layers:
        return None
    leaves = leaves_of(prog, "jit_pt_decode_block")
    if leaves is None:
        return None
    seconds = sum(o.t1 - o.t0 for o in leaves if trace.is_pallas(o.name)
                  and "pt_paged_decode" in o.name)
    page = int(run.cell.spec["engine"]["page_size"])
    need = 0.0
    for n, grown, _ in log:
        for j in range(n):
            need += layers * paged_decode.paged_decode_bytes(
                [ctx + j + 1 for ctx, k in grown if k > j],
                cfg["num_key_value_heads"], cfg["num_attention_heads"],
                cfg["head_dim"], page)
    if seconds <= 0 or need <= 0:
        return None
    return 100.0 * need / run.device["peaks"]["hbm_bytes_per_s"] / seconds
