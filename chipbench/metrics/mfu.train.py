"""Model: FLOPs a trained token needs (chipbench/ops/decoder_flops.py: 6 x
matmul parameters + causal attention, recomputation not counted) x tokens/s
per chip over the chip's published bf16 peak."""

from chipbench.ops import decoder_flops


def read(run):
    tok_s = run.e2e.get("train_tok_s_chip")
    if not tok_s:
        return None
    per_token = decoder_flops.train_flops_per_token(
        run.cell.config, int(run.cell.traffic["seq_len"]))
    return 100.0 * per_token * tok_s / run.device["peaks"]["bf16_flops"]
