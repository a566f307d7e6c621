"""The chat generator with ONE order of sizes for every seed, jax-free.

``chat`` gives every seed the same sizes in another order, and a closed loop
that a window cuts off after six rounds of long prompts then measures the
order: which prompts share a packed call, which sequences are mid-prefill
when the window ends. Here the sizes, their pairing, the greedy flags and
the tenants are ``chat``'s at the traffic file's ``order_seed``, the same in
every run, and ``--seed`` draws what a request holds: the tenants' prefixes,
every prompt's token ids and every request's sampling seed (and, in the
harness, the weights). Two seeds then ask the engine for the same steps on
other numbers, and what is left of their spread is the machine's.

    generate(traffic, seed, vocab) -> Schedule
"""

from __future__ import annotations

import numpy as np

from chipbench.generators import chat


def generate(traffic: dict, seed: int, vocab: int) -> chat.Schedule:
    sched = chat.generate(traffic, int(traffic["order_seed"]), vocab)
    shared = int(traffic["shared_prefix"])
    page = int(traffic.get("unshared_tail_min", 16))
    lo_id = int(traffic.get("first_token_id", 3))
    tok = chat._rng(seed, 1)
    prefixes = tok.integers(lo_id, vocab, (int(traffic["tenants"]), shared)
                            ).astype(np.int32)
    for r in sched.requests:
        length = len(r.prompt)
        head = min(shared, max(0, length - page))
        tail = tok.integers(lo_id, vocab, length - head).astype(np.int32)
        r.prompt = np.concatenate([prefixes[r.tenant, :head], tail])
        r.seed = int(tok.integers(1, 2 ** 31 - 1))
    return sched
