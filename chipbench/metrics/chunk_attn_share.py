"""Model: the attention layers' share of the packed prefill chunk: leaf-op
device time under ``pt.attn`` (projections, QK-norm, rotary, the gate, and
the dense gather with its float32 scores where no kernel serves the chunk),
``pt.kv_write`` (the append) or the kernel ``pt_paged_chunk``, over
``jit_pt_prefill_chunk``."""

from chipbench.metrics._scopes import share_of


def read(run):
    return share_of(run, "jit_pt_prefill_chunk",
                    ("pt.attn", "pt.kv_write", "pt_paged_chunk"))
