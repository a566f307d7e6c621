"""Model: the window attention layers' share of the decode block: leaf-op
device time under ``pt.attn.window`` (projections, QK-norm, rotary, the
output gate, and inside it the append ``pt.kv_write`` and the kernel
``pt_paged_decode``, which walks the window's pages only), over
``jit_pt_decode_block``. Nothing to read in a program without the scope."""

from chipbench.metrics._scopes import share_of


def read(run):
    return share_of(run, "jit_pt_decode_block", ("pt.attn.window",))
