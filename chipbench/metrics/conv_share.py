"""Model: the gated short convolutions' share of the decode block: leaf-op
device time under ``pt.conv`` (projections, the taps, the gates) or
``pt.state_write`` (the ring's update), over ``jit_pt_decode_block``."""

from chipbench.metrics._scopes import share_of


def read(run):
    return share_of(run, "jit_pt_decode_block",
                    ("pt.conv", "pt.state_write"))
