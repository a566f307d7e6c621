"""The chat generator: seeded requests for a serving cell, jax-free.

After ``paddle_tpu/observability/workload.py`` (``generate_schedule``:
clipped-lognormal lengths, tenants with shared system prefixes), with one
change that the measurement needs: every seed gets the
SAME sizes, in another order. Requests come in rounds of ``round``: the
prompt lengths of every round are the ``round`` quantiles of the clipped
lognormal on an even grid, so are its answer lengths, and the seed permutes
each within the round, pairs them, and draws the token ids. Whatever part
of the stream a window consumes, two seeds have offered it the same amount
of work, to within a round, and differ in interleaving. Arrivals are a
closed loop's (``arrival.clients`` callers that each resubmit at once); an
open-loop mix brings its arrival grid with the cell that first sends one.

    generate(traffic, seed, vocab) -> Schedule
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as planned: the driver builds the program's Request."""
    index: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    greedy: bool
    seed: int
    tenant: int


@dataclasses.dataclass
class Schedule:
    requests: list          # Planned, in order of submission
    arrival: dict           # the traffic file's "arrival" block
    sampling: dict
    eos_token_id: int

    def fingerprint(self) -> bytes:
        """Bytes that are identical for identical schedules."""
        h = [np.asarray([r.index, r.max_new, r.greedy, r.seed, r.tenant],
                        np.int64).tobytes() + r.prompt.tobytes()
             for r in self.requests]
        return b"".join(h)


def lognormal_grid(n: int, median: float, sigma: float, lo: int, hi: int):
    """n quantiles of a lognormal, clipped to [lo, hi], as whole numbers."""
    nd = statistics.NormalDist()
    q = [math.exp(math.log(median) + sigma * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), lo, hi).astype(np.int64)


def _rng(seed: int, stream: int):
    return np.random.Generator(np.random.PCG64([int(seed), stream]))


def generate(traffic: dict, seed: int, vocab: int) -> Schedule:
    n, size = int(traffic["requests"]), int(traffic["round"])
    if n % size:
        raise ValueError(f"requests {n} is not a whole number of rounds "
                         f"of {size}")
    p, o = traffic["prompt"], traffic["output"]
    rng = _rng(seed, 0)
    grid_p = lognormal_grid(size, p["median"], p["sigma"], p["min"], p["max"])
    grid_o = lognormal_grid(size, o["median"], o["sigma"], o["min"], o["max"])
    prompts = np.concatenate([rng.permutation(grid_p)
                              for _ in range(n // size)])
    outputs = np.concatenate([rng.permutation(grid_o)
                              for _ in range(n // size)])
    arrival = traffic["arrival"]
    if arrival["kind"] != "closed":
        raise ValueError(f"arrival kind {arrival['kind']!r}: the chat "
                         f"generator sends closed loops only")
    tenants = int(traffic["tenants"])
    shared = int(traffic["shared_prefix"])
    page = int(traffic.get("unshared_tail_min", 16))
    tok = _rng(seed, 1)
    lo_id = int(traffic.get("first_token_id", 3))
    prefixes = tok.integers(lo_id, vocab, (tenants, shared)).astype(np.int32)
    every = int(traffic.get("greedy_every", 0))
    greedy = np.zeros(n, bool)          # the same number in every round
    if every:
        for lo in range(0, n, size):
            greedy[lo + rng.permutation(size)[::every]] = True
    reqs = []
    for i in range(n):
        length = int(prompts[i])
        tenant = int(i % tenants)
        head = min(shared, max(0, length - page))
        tail = tok.integers(lo_id, vocab, length - head).astype(np.int32)
        reqs.append(Planned(
            index=i, prompt=np.concatenate([prefixes[tenant, :head], tail]),
            max_new=int(outputs[i]), greedy=bool(greedy[i]),
            seed=int(tok.integers(1, 2 ** 31 - 1)), tenant=tenant))
    return Schedule(requests=reqs, arrival=arrival,
                    sampling=traffic["sampling"],
                    eos_token_id=int(traffic["eos_token_id"]))
