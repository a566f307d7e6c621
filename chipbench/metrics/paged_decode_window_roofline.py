"""Kernels: the paged decode kernel against its memory roofline in a model
whose layers do not agree about what a row's context is
(``layer_types``: ``sliding_attention`` layers read the last
``sliding_window`` positions, ``full_attention`` layers all), read inside
decode blocks of any mix of rows. Time: the Pallas custom calls named
``pt_paged_decode`` inside the executions of ``jit_pt_decode_block`` in the
traced window. Bytes: for every row's context at every token step of the
traced engine steps (the driver's ``steps_log``: a row that grew ``k``
tokens was read at ``k`` steps, its context one longer each; the context a
call sees holds the position it has just written), the pages a window
layer's call reads (``chipbench/ops/paged_decode_window.py``) times the
window layers, and the pages of the whole context
(``chipbench/ops/paged_decode.py``) times the full ones: one call a layer a
token step. A row that met its EOS inside a block is still read to the
block's end and not counted: the share errs low. Memory-bound: bytes over
the chip's peak bandwidth is the least time."""

from chipbench.harness import trace
from chipbench.metrics import _program
from chipbench.metrics._scopes import leaves_of
from chipbench.ops import paged_decode, paged_decode_window


def read(run):
    prog, log = _program.of(run), run.window.get("steps_log")
    cfg = run.cell.config
    kinds = list(cfg.get("layer_types", ()))
    n_window = kinds.count("sliding_attention")
    n_full = kinds.count("full_attention")
    if prog is None or not log or not n_window:
        return None
    leaves = leaves_of(prog, "jit_pt_decode_block")
    if leaves is None:
        return None
    seconds = sum(o.t1 - o.t0 for o in leaves if trace.is_pallas(o.name)
                  and "pt_paged_decode" in o.name)
    page = int(run.cell.spec["engine"]["page_size"])
    shape = (cfg["num_key_value_heads"], cfg["num_attention_heads"],
             cfg["head_dim"], page)
    need = 0.0
    for n, grown, _ in log:
        for j in range(n):
            ctx = [c + j for c, k in grown if k > j]
            need += n_window * paged_decode_window.paged_decode_window_bytes(
                ctx, int(cfg["sliding_window"]), *shape)
            need += n_full * paged_decode.paged_decode_bytes(ctx, *shape)
    if seconds <= 0 or need <= 0:
        return None
    return 100.0 * need / run.device["peaks"]["hbm_bytes_per_s"] / seconds
