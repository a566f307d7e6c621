"""The driver of a serving cell: a closed loop of clients that each resubmit
at once, over the program's public engine API:
``ContinuousBatchingEngine(...)``, ``Request(...)``, ``add_request``,
``step``, ``has_work``, ``req.output / done / failed``.

Every time is the benchmark's own clock at the moment ``req.output`` was
seen to have grown, after the ``step()`` that grew it returned: with an EOS
id on every request the engine materialises each block of tokens on the
host inside ``step()``.
"""

from __future__ import annotations

import dataclasses
import gc

import numpy as np

from .clock import now


@dataclasses.dataclass
class Live:
    plan: object
    req: object
    sent: float
    t_first: float = 0.0
    t_last: float = 0.0
    seen: int = 0


def build_engine(cell, model):
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    e = dict(cell.spec["engine"])
    prefix = e.pop("prefix_cache", None)
    if prefix:
        e["prefix_cache"] = PrefixCacheConfig(**prefix)
    return ContinuousBatchingEngine(model, **e)


def make_request(plan, sched):
    from paddle_tpu.inference.serving import Request

    s = sched.sampling
    if plan.greedy:
        # greedy in effect: temperature 0 or, where the mix says so, a
        # temperature so low that the sampler's softmax is one-hot (the
        # batch then never falls to the all-greedy decode program)
        t = float(s.get("greedy_temperature", 0.0))
        kw = dict(temperature=t, top_p=1.0) if t > 0 else {}
    else:
        kw = dict(temperature=float(s["temperature"]),
                  top_p=float(s["top_p"]))
    return Request(plan.prompt, max_new_tokens=plan.max_new,
                   eos_token_id=sched.eos_token_id, seed=plan.seed,
                   tenant=str(plan.tenant), **kw)


def warm_up(engine, cell, vocab: int, eos: int, sampling: dict, spans):
    """Touch every program the window can meet, through the public API: a
    decode scan of each length 1..block_size, greedy and sampled (a lone
    request of n+1 tokens decodes one block of n), then admission waves of
    2, 4, ... max_batch requests, greedy and sampled, for the packed
    prefill and first-token programs of each power-of-two width."""
    from paddle_tpu.inference.serving import Request

    e = cell.spec["engine"]
    rng = np.random.Generator(np.random.PCG64(12345))
    samp = dict(temperature=float(sampling["temperature"]),
                top_p=float(sampling["top_p"]))

    def wave(k, max_new, sampled):
        reqs = [Request(rng.integers(3, vocab, 2 * e["page_size"]
                                     ).astype(np.int32),
                        max_new_tokens=max_new, eos_token_id=eos,
                        seed=int(rng.integers(1, 2 ** 31 - 1)),
                        **(samp if sampled else {})) for _ in range(k)]
        for r in reqs:
            engine.add_request(r)
        while engine.has_work():
            engine.step()
        engine.finished()
        bad = [r for r in reqs if r.failed or not r.done]
        if bad:
            raise RuntimeError(f"warm-up: {len(bad)} of {k} requests failed: "
                               f"{bad[0].error}")

    # with a positive greedy_temperature no batch is ever all-greedy, and
    # the greedy variants of the programs are never met
    modes = (True,) if float(sampling.get("greedy_temperature", 0)) > 0 \
        else (False, True)
    with spans.span("warm_up"):
        for sampled in modes:
            for n in range(1, int(e["block_size"]) + 1):
                wave(1, n + 1, sampled)
        k = 2
        widths = []
        while k < e["max_batch"]:
            widths.append(k)
            k *= 2
        for k in widths + [int(e["max_batch"])]:
            for sampled in modes:
                wave(k, 2, sampled)


class Sampler:
    """Per-step readings of the engine's own state, for the per-layer
    readers. Each reading is optional: an attribute the program no longer
    has gives no sample, and the reader then returns nothing."""

    def __init__(self, engine):
        self.engine = engine
        self.occupancy = []
        self.pool_used = []

    def sample(self):
        e = self.engine
        occ = getattr(e, "active_slots", None)
        if callable(occ):
            self.occupancy.append(occ() / e.max_batch)
        alloc = getattr(e, "_alloc", None)
        if alloc is not None and hasattr(alloc, "free_blocks"):
            self.pool_used.append(
                1.0 - alloc.free_blocks / max(1, alloc.num_blocks))

    def queued(self):
        q = getattr(self.engine, "_queue", None)
        return None if q is None else {id(r) for r in q}


def tpots_ms(win) -> list:
    """Per request (t_last - t_first) / (tokens - 1), over every request
    that had two tokens or more on the host when the window closed,
    finished or not: the long answers never finish inside a window."""
    return [(lv.t_last - lv.t_first) / (lv.seen - 1) * 1e3
            for lv in win["done"] + win["unfinished"]
            if not lv.req.failed and lv.seen > 1]


def drive(engine, sched, seconds: float, spans, tracer=None):
    """The measured window. Returns the Live records and window facts."""
    plans = iter(sched.requests)
    live, done = [], []
    sampler = Sampler(engine)
    stats0 = dict(engine.stats)
    tokens_in_window = 0
    t_tokens = 0.0      # the last poll inside the window that saw tokens
    exhausted = False

    def submit(plan):
        req = make_request(plan, sched)
        t = now()
        with spans.span("submit"):
            engine.add_request(req)
        live.append(Live(plan, req, t))

    gc.collect()
    gc.disable()        # no collector pause inside the window
    t0 = now()
    t_end = t0 + seconds
    for _ in range(int(sched.arrival["clients"])):
        submit(next(plans))
    steps = 0
    step_s = []         # seconds of every engine.step(), for the info line
    steps_log = []      # per traced step: (block length, contexts before)
    while now() < t_end and engine.has_work():
        if tracer is not None:
            tracer.tick(now() - t0)
        traced = tracer is not None and tracer.state == "tracing"
        if traced:
            before = [(lv, lv.seen) for lv in live if lv.seen]
            queued = sampler.queued() or set()
            waiting = sum(1 for lv in live if id(lv.req) in queued)
            # nothing mid-prefill, and nothing that could be admitted
            pure = (len(before) + waiting == len(live) and (
                not waiting or len(before) >= engine.max_batch))
        t_step = now()
        with spans.span("engine.step"):
            engine.step()
        steps += 1
        step_s.append(now() - t_step)
        if traced:
            grown = [(len(lv.plan.prompt) + seen,
                      len(lv.req.output) - seen) for lv, seen in before]
            pure = pure and not any(lv.req.done for lv, _ in before)
            steps_log.append((max((g for _, g in grown), default=0),
                              grown, pure))
        with spans.span("poll"):
            t = now()
            sampler.sample()
            polled, live[:] = live[:], []   # submit() appends the new ones
            for lv in polled:
                n = len(lv.req.output)
                if n > lv.seen:
                    if t <= t_end:
                        tokens_in_window += n - lv.seen
                        t_tokens = t
                    if lv.seen == 0:
                        lv.t_first = t
                    lv.t_last = t
                    lv.seen = n
                if lv.req.done:
                    done.append(lv)
                    if t < t_end:
                        nxt = next(plans, None)
                        if nxt is None:
                            exhausted = True
                        else:
                            submit(nxt)
                else:
                    live.append(lv)
        engine.finished()
    window = now() - t0
    gc.enable()
    if tracer is not None:
        tracer.close()
    stats1 = dict(engine.stats)
    return dict(done=done, unfinished=list(live), window_s=window,
                asked_s=seconds, steps=steps, step_s=step_s,
                steps_log=steps_log,
                tokens=tokens_in_window, t_tokens=t_tokens,
                exhausted=exhausted, stats0=stats0, stats1=stats1,
                sampler=sampler, sent=len(done) + len(live))
