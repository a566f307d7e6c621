"""Scheduler and admission: the mean length of a decode block, in token
steps, over the window (the engine's exact counts): blocks are cut short
whenever a row is about to finish, and each pays the block's fixed cost."""


def read(run):
    s0, s1 = run.window["stats0"], run.window["stats1"]
    if "decode_blocks" not in s1 or "decode_blocks" not in s0:
        return None
    blocks = s1["decode_blocks"] - s0["decode_blocks"]
    if blocks <= 0:
        return None
    return (s1["decode_block_steps"] - s0["decode_block_steps"]) / blocks
