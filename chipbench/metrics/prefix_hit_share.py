"""Cache manager: prompt tokens served from cached pages over all prompt
tokens admitted in the window (the engine's exact counts)."""


def read(run):
    s0, s1 = run.window["stats0"], run.window["stats1"]
    if "hit_tokens" not in s1:
        return None
    hit = s1["hit_tokens"] - s0["hit_tokens"]
    miss = s1["miss_tokens"] - s0["miss_tokens"]
    return 100.0 * hit / (hit + miss) if hit + miss else None
