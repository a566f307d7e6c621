"""Driver of a training cell over the program's public trainer:
``Engine(model, mesh, lr=, clip_norm=)``, ``eng.step(ids, labels)``,
``eng.params`` / ``eng.m`` (read once, for the check).

One object — the engine with its compiled step and state — is built in
set-up, driven through its first steps by ``feed_step`` (the window's own
call), and handed to the window. A step counts when ``block_until_ready``
on its loss returned inside the window; the next batch is prepared on the
host while the device runs the step.
"""

from __future__ import annotations

import gc

from . import check
from .clock import now

HYPER = dict(beta1=0.9, beta2=0.95, epsilon=1e-8, weight_decay=0.1)


def hyper_of(cell) -> dict:
    t = cell.spec["trainer"]
    return dict(HYPER, lr=float(t["lr"]), clip_norm=float(t["clip_norm"]))


def build(cell, seed: int):
    """The program's trainer on the benchmark's seeded weights."""
    from paddle_tpu.distributed.auto_parallel import Engine

    from . import weights as W

    t = cell.spec["trainer"]
    model = cell.adapter.build_model(
        cell.config, max_positions=int(cell.traffic["seq_len"]),
        recompute=bool(t.get("recompute", False)))
    cell.adapter.assign(model, W.model_weights(cell.leaf_table, seed))
    h = hyper_of(cell)
    return Engine(model, mesh=None, lr=h["lr"], clip_norm=h["clip_norm"],
                  beta1=h["beta1"], beta2=h["beta2"], epsilon=h["epsilon"],
                  weight_decay=h["weight_decay"])


class Feed:
    """The window's call and feed: ``step()`` dispatches one train step on
    the batch that is ready, prepares the next on the host meanwhile, then
    fences. Returns (loss, seconds from call to dispatch returned)."""

    def __init__(self, eng, batches, spans):
        self.eng, self.batches, self.spans = eng, batches, spans
        self.ready = self._prepare()

    def _prepare(self):
        with self.spans.span("batch"):
            ids = next(self.batches)
            return self.eng.shard_batch(ids)

    def step(self):
        import jax

        ids = self.ready
        t0 = now()
        with self.spans.span("eng.step"):
            loss = self.eng.step(ids, ids)
        enqueue = now() - t0
        self.ready = self._prepare()
        with self.spans.span("fence"):
            loss = float(jax.block_until_ready(loss))
        return loss, enqueue


def first_steps(cell, eng, feed, seed: int, names):
    """Steps one and two through the window's own feed, reading what the
    check compares. Returns the program's numbers."""
    h = hyper_of(cell)
    loss1, _ = feed.step()
    gn, vec = check.program_norms(cell.adapter, names, eng.m,
                                  scale=1.0 / (1.0 - h["beta1"]))
    loss2, _ = feed.step()
    dn = check.program_change(cell.adapter, names, eng.params,
                              cell.leaf_table, seed)
    return {"loss": [loss1, loss2], "grad_norm": gn, "gain_grad": vec,
            "change_norm": dn}


def param_names(eng):
    """Names of ``eng.params`` in order: the model's trainable parameters."""
    return [n for n, p in eng.model.named_parameters()
            if not p.stop_gradient]


def drive(feed, seconds: float, tracer=None):
    losses, enqueues, ends = [], [], []
    gc.collect()
    gc.disable()        # no collector pause inside the window
    t0 = now()
    t_end = t0 + seconds
    while True:
        t = now()
        if t >= t_end:
            break
        if tracer is not None:
            tracer.tick(t - t0)
        loss, enq = feed.step()
        ends.append(now())
        losses.append(loss)
        enqueues.append(enq)
    gc.enable()
    if tracer is not None:
        tracer.close()
    inside = sum(1 for e in ends if e <= t_end)
    return dict(losses=losses, enqueues=enqueues, steps_inside=inside,
                steps=len(ends), window_s=now() - t0, asked_s=seconds,
                t0=t0, ends=ends)
