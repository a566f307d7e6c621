"""Model: the full attention layers' share of the decode block, in a model
that also has window layers: leaf-op device time under ``pt.attn.full``
(projections, QK-norm, the output gate, and inside it the append
``pt.kv_write`` and the kernel ``pt_paged_decode`` over the whole context),
over ``jit_pt_decode_block``. Nothing to read in a program without the
scope."""

from chipbench.metrics._scopes import share_of


def read(run):
    return share_of(run, "jit_pt_decode_block", ("pt.attn.full",))
