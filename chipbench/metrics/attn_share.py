"""Model: the attention layers' share of the decode block: leaf-op device
time under ``pt.attn`` (projections, QK-norm, rotary, and the dense gather
that serves heads the paged kernel does not take), ``pt.kv_write`` (the
append) or the kernel ``pt_paged_decode``, over ``jit_pt_decode_block``."""

from chipbench.metrics._scopes import share_of


def read(run):
    return share_of(run, "jit_pt_decode_block",
                    ("pt.attn", "pt.kv_write", "pt_paged_decode"))
