"""Kernels: the state-space layers' one-token step against its memory
roofline. Time: inside the executions of ``jit_pt_decode_block`` in the
traced window, the device time in which the bytes counted move: the leaf
ops under ``pt.ssm.step`` (the state's update and read-out) and
``pt.ssm.conv`` (the window's update and the taps), and the compiler's
unnamed copies of the two pools (``metrics/_ssm.py``, the set ``ssm_share``
takes too), each as a TRANSFER, from its ``-start`` to the end of its
``-done``: the length of the union of all these intervals. A ``-done``
alone is a wait, and a copy better hidden behind other work would wait
less and read over 100%; a transfer's whole span cannot, so 100% is the
pools passing at peak bandwidth and nothing else running meanwhile. Bytes:
``chipbench/ops/ssd.py``'s ``step_bytes`` (state read and written, conv
window, the token's inputs and output) for every row that decoded at every
token step of the traced engine steps (the driver's ``steps_log``: tokens
grown a row, so a free slot or a row past its EOS counts nothing although
the pass rewrites its state too), times the Mamba-2 layers. Memory-bound:
bytes over the chip's peak bandwidth is the least time."""

from chipbench.harness.trace import union
from chipbench.metrics import _program, _ssm
from chipbench.metrics._scopes import leaves_of, under
from chipbench.ops import ssd


def read(run):
    prog, log = _program.of(run), run.window.get("steps_log")
    if prog is None or not log:
        return None
    leaves = leaves_of(prog, "jit_pt_decode_block")
    if leaves is None:
        return None
    named, copies = _ssm.ops_of(run, leaves)
    mine = [o for o in named if under(o, ("pt.ssm.step", "pt.ssm.conv"))]
    if not mine:
        return None
    seconds = sum(b - a for a, b in union(
        [(o.t0, o.t1) for o in mine] + _ssm.transfers(copies)))
    row_steps = sum(k for _, grown, _ in log for _, k in grown)
    if seconds <= 0 or row_steps <= 0:
        return None
    cfg = run.cell.config
    dims = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"])
    layers = cfg["hybrid_override_pattern"].count("M")
    need = row_steps * layers * ssd.step_bytes(*dims, cfg["conv_kernel"])
    return 100.0 * need / run.device["peaks"]["hbm_bytes_per_s"] / seconds
