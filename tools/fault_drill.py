"""Fault drill: prove every recovery path by injecting its fault.

Each drill runs a small end-to-end scenario twice: with its recovery path
enabled (the injected fault must be absorbed) and with it disabled (the
same fault must flip the exit code). ``--selftest`` runs the whole seeded
matrix — heartbeat loss, store stall, checkpoint shard corruption, serving
engine saturation, serving deadline, prefix-cache block-pool exhaustion,
128-slot big-batch saturation (docs/SERVING.md), speculative-decode
divergence (verification disabled — accept-all), the numeric
classes (NaN gradient, loss spike,
poisoned batch — docs/NUMERIC_GUARD.md), a composed multi-site chaos plan
(three subsystems faulted concurrently off ONE seed), and the full
checkpoint-lifecycle arc (train → async checkpoint → elastic shrink →
resume → publish-to-serving, docs/RESILIENCE.md) — and exits
0 iff every fault class recovers when enabled AND fails when its recovery
is off. For the numeric drills "recovery off" means GuardPolicy(action=
"warn"): detection stays on but the anomalous update is applied — exactly
the run an unguarded job would have. Recovery is proven by tests, not
prayer (docs/RESILIENCE.md).

Usage:
    python tools/fault_drill.py --selftest
    python tools/fault_drill.py --drill heartbeat            # expect exit 0
    python tools/fault_drill.py --drill heartbeat --no-recover   # expect != 0

Faults come from seeded, step-indexed FaultPlans
(paddle_tpu/distributed/resilience/faults.py), so every run injects the
same faults at the same events.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading
import time

# pure-Python store daemon so server-side faults (store.daemon stalls) are
# real, not simulated; CPU jax with 8 host devices for the elastic meshes
os.environ["PT_DISABLE_NATIVE"] = "1"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")

import _selftest  # noqa: E402

ROOT = _selftest.bootstrap()


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------

def _toy_model(d=8):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.nn.layer.layers import Layer

    class Toy(Layer):
        def __init__(self):
            super().__init__()
            self.fc = paddle.nn.Linear(d, d)

        def loss_fn(self, x, y):
            out = self.fc(Tensor(x))
            diff = out._data - y
            return (diff * diff).mean()

    return Toy()


_SERVING = {}


def _serving_model():
    """One tiny llama shared by the serving drills (build once)."""
    if "model" not in _SERVING:
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(11)
        cfg = LlamaConfig.tiny(num_hidden_layers=1)
        _SERVING["model"] = (cfg, LlamaForCausalLM(cfg))
    return _SERVING["model"]


# ---------------------------------------------------------------------------
# drill: heartbeat loss -> elastic save/reshard/resume
# ---------------------------------------------------------------------------

def drill_heartbeat(recover: bool):
    """2-node elastic run loses its peer mid-run. Recovery = detect the
    stale heartbeat, checkpoint, rebuild the mesh over the survivor,
    resume at the recorded step; the final loss must match an uninterrupted
    run (deterministic per-step data => replay-exact trajectory)."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.distributed.communication.store import TCPStore
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.resilience import (FaultPlan, FaultSpec,
                                                   ResilientTrainer)

    D, B, STEPS = 8, 8, 8

    def data_fn(step):
        rng = np.random.default_rng(1000 + step)
        return (rng.standard_normal((B, D)).astype(np.float32),
                rng.standard_normal((B, D)).astype(np.float32))

    def build(alive):
        n = 8 if len(alive) >= 2 else 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
        paddle.seed(0)
        return Engine(_toy_model(D), mesh, lr=0.05, clip_norm=None)

    with tempfile.TemporaryDirectory() as tmp:
        # uninterrupted reference trajectory (2-node mesh, no faults)
        ref = ResilientTrainer(lambda alive: build(["a", "b"]),
                               os.path.join(tmp, "ref"), elastic=None,
                               save_every=100, async_save=False
                               ).fit(data_fn, STEPS)
        ref_final = ref["losses"][STEPS]

        store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1,
                         timeout=20.0)
        store_b = TCPStore("127.0.0.1", store.port, world_size=1,
                           timeout=20.0)
        plan = FaultPlan(seed=7, specs=[
            FaultSpec("elastic.heartbeat", "kill", at=3, count=-1,
                      match="nodeB")])
        mgr_b = ElasticManager(store_b, "drill", "nodeB",
                               expected=["nodeA", "nodeB"],
                               heartbeat_interval=0.1, ttl=0.45)
        mgr_a = ElasticManager(store, "drill", "nodeA",
                               expected=["nodeA", "nodeB"],
                               heartbeat_interval=0.1, ttl=0.45) \
            if recover else None
        b_stop = threading.Event()

        def node_b_loop():
            i = 0
            while not b_stop.is_set():
                if mgr_b._thread is None or not mgr_b._thread.is_alive():
                    return              # heartbeat killed -> node is dead
                if i >= 3:
                    # deterministic backstop: whatever the thread-scheduling
                    # weather, node B is dead by step 3 — its lease counter
                    # stops advancing and it leaves the per-step barriers,
                    # so A's recovery path MUST engage (wall-clock-only
                    # death made this drill flake under heavy CI load)
                    mgr_b.stop()
                    return
                try:
                    store_b.barrier(f"g2s{i}", world_size=2, timeout=3.0)
                except Exception:
                    return
                i += 1

        def coop_data_fn(step):
            # the job's per-step cross-node sync: a dead peer turns this
            # into a timeout — exactly how peer loss surfaces in real runs
            ws = len(mgr_a.expected) if mgr_a is not None else 2
            if ws > 1:
                store.barrier(f"g2s{step}", world_size=ws, timeout=1.5)
            time.sleep(0.05)
            return data_fn(step)

        plan.install()
        try:
            mgr_b.start()
            if mgr_a is not None:
                mgr_a.start()
            b_thread = threading.Thread(target=node_b_loop, daemon=True)
            b_thread.start()
            trainer = ResilientTrainer(build, os.path.join(tmp, "job"),
                                       elastic=mgr_a, save_every=2)
            try:
                out = trainer.fit(coop_data_fn, STEPS)
            except Exception as e:
                return False, f"run died without recovery: {type(e).__name__}: {e}"
            finally:
                b_stop.set()
                if mgr_a is not None:
                    mgr_a.stop()
                mgr_b.stop()
        finally:
            plan.uninstall()
            store_b.close()
            store.close()
        if out["restarts"] < 1:
            return False, "peer loss never detected (no restart)"
        final = out["losses"][STEPS]
        if not np.allclose(final, ref_final, rtol=1e-3):
            return (False, f"post-resume trajectory diverged: {final} vs "
                    f"uninterrupted {ref_final}")
        return True, (f"peer lost, resumed at step {out['resumed_at']}, "
                      f"final loss {final:.6f} == uninterrupted {ref_final:.6f}")


# ---------------------------------------------------------------------------
# drill: store stall -> retry/timeout/backoff
# ---------------------------------------------------------------------------

def drill_store_stall(recover: bool):
    """The store daemon stalls one op past the client's op deadline.
    Recovery = socket timeout -> reconnect -> retry (PT-RETRY policy);
    without retry the first stalled op raises."""
    from paddle_tpu.distributed.communication.store import TCPStore
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec

    plan = FaultPlan(seed=3, specs=[
        FaultSpec("store.daemon", "stall", at=2, count=1, arg=1.2)])
    prev = os.environ.get("PT_RETRY_DISABLE")
    if not recover:
        os.environ["PT_RETRY_DISABLE"] = "1"
    store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1,
                     timeout=10.0, op_timeout=0.4)
    try:
        with plan:
            for i in range(6):
                store.set(f"k{i}", str(i).encode())
                got = store.get(f"k{i}", wait=False)
                if got != str(i).encode():
                    return False, f"k{i}: got {got!r}"
        stalled = [e for e in plan.log if e[2] == "stall"]
        if not stalled:
            return False, "fault never fired"
        return True, f"rode through daemon stall at {stalled[0][1]!r}"
    except Exception as e:
        return False, f"store op failed: {type(e).__name__}: {e}"
    finally:
        store.close()
        if prev is None:
            os.environ.pop("PT_RETRY_DISABLE", None)
        else:
            os.environ["PT_RETRY_DISABLE"] = prev


# ---------------------------------------------------------------------------
# drill: checkpoint shard corruption -> checksum detect + replica recover
# ---------------------------------------------------------------------------

def drill_shard_corruption(recover: bool):
    """A shard is truncated on disk after its digests were recorded.
    Recovery = load-time verification raises CheckpointCorruptionError
    *naming the shard*, and a replica copy restores the data. With
    verification off the corruption surfaces as an untyped decoder error
    (or silently wrong weights)."""
    import numpy as np

    from paddle_tpu.distributed.checkpoint import (CheckpointCorruptionError,
                                                   load_state_dict,
                                                   save_state_dict)
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec

    w = np.arange(4096, dtype=np.float32)

    def fault():
        return FaultPlan(seed=5, specs=[
            FaultSpec("checkpoint.shard", "truncate", at=0, count=1, arg=64)])

    with tempfile.TemporaryDirectory() as tmp:
        p1 = os.path.join(tmp, "c1")
        with fault():
            save_state_dict({"w": w}, p1)
        target = {"w": np.zeros_like(w)}
        if not recover:
            try:
                load_state_dict(target, p1, verify=False)
            except CheckpointCorruptionError:
                return True, "unexpected: typed error with verification off"
            except Exception as e:
                return (False, "verification off: untyped failure "
                        f"{type(e).__name__} (shard not named)")
            if np.array_equal(np.asarray(target["w"]), w):
                return False, "truncated shard read back clean?!"
            return False, "corrupt shard loaded silently"
        try:
            load_state_dict(target, p1)
            return False, "corruption not detected"
        except CheckpointCorruptionError as e:
            if "0_0.distcp" not in str(e):
                return False, f"bad shard not named: {e}"
            detected = str(e)
        # replica copy -> transparent recovery
        p2 = os.path.join(tmp, "c2")
        with fault():
            save_state_dict({"w": w}, p2, replica=True)
        target2 = {"w": np.zeros_like(w)}
        load_state_dict(target2, p2)
        if not np.array_equal(np.asarray(target2["w"]), w):
            return False, "replica recovery returned wrong data"
        return True, f"detected ({detected.split(':')[0]}), replica recovered"


# ---------------------------------------------------------------------------
# drill: serving engine saturation -> bounded-queue backpressure
# ---------------------------------------------------------------------------

def drill_engine_saturation(recover: bool):
    """Admission flood past the queue high-water mark. Recovery =
    EngineSaturated backpressure keeps the queue bounded while admitted
    requests decode to completion; without it the queue grows unbounded."""
    import numpy as np

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              EngineSaturated, Request)

    cfg, m = _serving_model()
    eng = ContinuousBatchingEngine(m, max_batch=1, max_len=32, page_size=8,
                                   max_queue=2 if recover else None)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
                    max_new_tokens=2) for _ in range(6)]
    admitted, rejected = [], 0
    for r in reqs:
        try:
            eng.add_request(r)
            admitted.append(r)
        except EngineSaturated:
            rejected += 1
    depth = len(eng._queue)
    eng.run_until_done()
    if rejected == 0:
        return False, f"no backpressure: queue grew to {depth}"
    if depth > 2:
        return False, f"queue exceeded high-water mark: {depth}"
    bad = [r.rid for r in admitted
           if not r.done or r.failed or len(r.tokens) != 2]
    if bad:
        return False, f"admitted requests did not complete: {bad}"
    return True, (f"{rejected} rejected at high-water 2, "
                  f"{len(admitted)} admitted all completed")


# ---------------------------------------------------------------------------
# drill: serving deadline -> eviction, not a hung slot
# ---------------------------------------------------------------------------

def drill_serving_deadline(recover: bool):
    """One slot's request exceeds its deadline mid-decode. Recovery = the
    slot is evicted and the request reported failed while the other slot
    keeps decoding to completion."""
    import numpy as np

    from paddle_tpu.inference.serving import ContinuousBatchingEngine, Request

    cfg, m = _serving_model()
    eng = ContinuousBatchingEngine(m, max_batch=2, max_len=64, page_size=8)
    rng = np.random.default_rng(1)
    fast = Request(rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32),
                   max_new_tokens=12)
    doomed = Request(rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32),
                     max_new_tokens=30,
                     deadline_s=0.15 if recover else None)
    eng.add_request(fast)
    eng.add_request(doomed)
    eng.step()
    time.sleep(0.2)                     # doomed's deadline expires mid-run
    eng.run_until_done(max_steps=200)
    if not recover:
        if doomed.failed:
            return True, "unexpected: evicted without a deadline"
        return False, ("no deadline: slow request ran to completion "
                       f"({len(doomed.tokens)} tokens), slot hogged")
    if not doomed.failed or not doomed.done:
        return False, "deadline-exceeded request not marked failed"
    if doomed.error is None or "deadline" not in doomed.error:
        return False, f"failure not attributed to deadline: {doomed.error!r}"
    if len(doomed.tokens) >= 30:
        return False, "evicted request decoded to completion anyway"
    if fast.failed or not fast.done or len(fast.tokens) != 12:
        return False, "healthy slot disturbed by the eviction"
    return True, (f"evicted after {len(doomed.tokens)} tokens "
                  f"({doomed.error}); other slot finished 12/12")


# ---------------------------------------------------------------------------
# drill: prefix-cache block-pool exhaustion -> backpressure, not corruption
# ---------------------------------------------------------------------------

def _overcommit(eng):
    """DRILL-ONLY: turn ``eng``'s allocator into what a refcount-less one
    is under exhaustion. When the pool cannot serve a request it hands
    over, oldest first, blocks that live tables still map, and never one
    the caller itself holds (admission pins the blocks it matched with an
    incref before it allocates: those read 2 and more). Stolen pages come
    first, so they become the thief's PROMPT blocks and its very next
    prefill overwrites a page the victim still reads. The drills assert
    the corruption; production admission defers instead."""
    from paddle_tpu.ops.paged_attention import BlockAllocator

    class Refcountless(BlockAllocator):
        def alloc(self, n, evict=None):
            got = super().alloc(n, evict=evict)
            if got is not None:
                return got
            mapped = [b for b, rc in self._ref.items() if rc == 1]
            free = super().alloc(min(n, self.free_blocks))
            if len(mapped) + len(free) < n:
                self.decref(free)
                return None
            stolen = mapped[:n - len(free)]
            self.incref(stolen)
            return stolen + free

    eng._alloc.__class__ = Refcountless


def drill_prefix_cache_exhaustion(recover: bool):
    """Seeded KV block-pool exhaustion mid-admission (docs/SERVING.md).

    A request is decoding with its prompt blocks registered in the radix
    prefix cache when the pool is exhausted under a second admission.
    Recovery = the refcounted allocator DEFERS the admission (the queue
    backs up into EngineSaturated) and serves it only once completed
    requests release blocks — both token streams exactly match generate().
    Without recovery (``_overcommit``: what a refcount-less
    allocator does) the second request is handed pages the first still
    reads, and the survivor's tokens are silently corrupted."""
    import numpy as np

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              EngineSaturated, Request)

    cfg, m = _serving_model()

    def ref(prompt, n):
        import paddle_tpu as paddle

        out = m.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n, temperature=0.0).numpy()[0]
        return [int(t) for t in out]

    rng = np.random.default_rng(5)
    pa = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    # pool: 2 slots * 4 pages; each request needs 3 (8 prompt + 16 new).
    # The fault holds 3 free blocks at B's admission -> 2 free + nothing
    # evictable (A holds its blocks) < 3 -> a correct allocator must defer.
    eng = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                   block_size=2, prefix_cache=True,
                                   max_queue=1)
    if not recover:
        _overcommit(eng)
    # an eos id no token equals: decode paces at block_size tokens a step
    # (without one a block runs block_size * 2^k steps), so A still has
    # most of its 16 tokens to decode over the stolen page when B arrives
    ra = Request(pa, max_new_tokens=16, eos_token_id=-1)
    rb = Request(pb, max_new_tokens=16, eos_token_id=-1)
    plan = FaultPlan(seed=9, specs=[
        FaultSpec("serving.block_pool", "exhaust", at=1, count=1, arg=3)])
    saturated = deferred = False
    with plan:
        eng.add_request(ra)
        eng.step()                  # A admitted; prefix registered
        eng.step()
        eng.add_request(rb)
        eng.step()                  # B's allocation hits the exhausted pool
        deferred = rb._n_out == 0 and len(eng._queue) == 1
        if deferred:
            try:
                eng.add_request(Request(pa, max_new_tokens=4))
            except EngineSaturated:
                saturated = True
        eng.run_until_done(max_steps=300)
    if not plan.log:
        return False, "exhaust fault never fired"
    ref_a = ref(pa, 16)
    if not recover:
        if ra.tokens == ref_a:
            return True, ("unexpected: overcommitted pool left shared "
                          "blocks intact")
        return False, ("no refcounted admission: pool overcommit handed "
                       "B pages A still reads — A's tokens corrupted "
                       f"({sum(x != y for x, y in zip(ra.tokens, ref_a))}"
                       f"/{len(ref_a)} wrong)")
    if not deferred:
        return False, "admission not deferred under exhaustion"
    if not saturated:
        return False, "backlog did not surface as EngineSaturated"
    if ra.tokens != ref_a:
        return False, "survivor's tokens corrupted despite refcounting"
    if rb.tokens != ref(pb, 16):
        return False, "deferred request served wrong tokens"
    return True, ("admission deferred at exhaustion, EngineSaturated "
                  "raised, both streams exact after blocks released "
                  f"({eng.stats['evictions']} LRU evictions)")


def drill_big_batch_saturation(recover: bool):
    """Seeded pool exhaustion mid-wave on a 128-slot engine
    (docs/SERVING.md mega-step section): a 6-request wave is decoding
    through the mega-step (device-resident tables, packed prefill)
    when the block pool is exhausted under a late admission.

    Recovery = the refcounted allocator DEFERS the admission (its table
    scatter never reaches the device), the queue backs up into
    EngineSaturated, and once the wave's blocks release the deferred
    request is served — every survivor's stream byte-identical to
    generate(). Without recovery (``_overcommit``) the late request
    is handed radix pages live tables still map; its packed prefill then
    overwrites k/v a decoding survivor reads mid-stream — silent
    corruption at 128 slots, exactly what the deferral exists to
    prevent."""
    import numpy as np

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              EngineSaturated,
                                              PrefixCacheConfig, Request)

    cfg, m = _serving_model()

    def ref(prompt, n):
        import paddle_tpu as paddle

        out = m.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=n, temperature=0.0).numpy()[0]
        return [int(t) for t in out]

    rng = np.random.default_rng(12)
    wave = [rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
            for _ in range(6)]
    pb = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    eng = ContinuousBatchingEngine(
        m, max_batch=128, max_len=40, page_size=8, block_size=4,
        prefix_cache=PrefixCacheConfig(prefill_chunk=8))
    if not recover:
        _overcommit(eng)
    wave_reqs = [Request(p, max_new_tokens=30) for p in wave]
    rb = Request(pb, max_new_tokens=30)
    # the wave's 6 admissions are block-pool events 0-5; the late
    # request's allocation is event 6 — the hold empties the free list
    # there, and the wave's own blocks are all live (nothing evictable)
    plan = FaultPlan(seed=9, specs=[
        FaultSpec("serving.block_pool", "exhaust", at=6, count=1,
                  arg=10 ** 6)])
    saturated = deferred = False
    with plan:
        for r in wave_reqs:
            eng.add_request(r)
        eng.step()                  # wave admitted + packed prefill (0-5)
        eng.step()                  # mega-step decoding, everyone live
        eng.max_queue = 1           # arm the saturation probe
        eng.add_request(rb)
        eng.step()                  # late allocation (event 6) hits the
        #                             emptied pool — every wave block is
        #                             live (rc >= 1), nothing evictable
        deferred = rb._n_out == 0 and len(eng._queue) == 1
        if deferred:
            try:
                eng.add_request(Request(pb, max_new_tokens=4))
            except EngineSaturated:
                saturated = True
        eng.run_until_done(max_steps=500)
    if not plan.log:
        return False, "exhaust fault never fired"
    refs = [ref(p, 30) for p in wave]
    wrong = [i for i, (r, w) in enumerate(zip(wave_reqs, refs))
             if list(r.tokens) != w]
    if not recover:
        if not wrong:
            return True, ("unexpected: overcommitted 128-slot pool left "
                          "live tables intact")
        return False, ("no refcounted deferral: the late admission stole "
                       f"pages {len(wrong)}/6 decoding survivors still "
                       "read — streams silently corrupted at 128 slots")
    if not deferred:
        return False, "late admission not deferred under exhaustion"
    if not saturated:
        return False, "backlog did not surface as EngineSaturated"
    if wrong:
        return False, (f"survivors {wrong} corrupted despite refcounting")
    if list(rb.tokens) != ref(pb, 30):
        return False, "deferred request served wrong tokens after release"
    return True, ("128-slot wave: admission deferred at exhaustion, "
                  "EngineSaturated raised, all 7 streams exact "
                  f"(packed_rows={eng.stats['packed_rows']}, "
                  f"fused_updates={eng.stats['fused_updates']})")


# ---------------------------------------------------------------------------
# numeric drills: health word + GuardPolicy (docs/NUMERIC_GUARD.md)
# ---------------------------------------------------------------------------

def _guarded_fixture(policy):
    """Toy guarded trainer pieces shared by the numeric drills."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import Engine

    D, B = 8, 8

    def data_fn(step):
        rng = np.random.default_rng(1000 + step)
        return (rng.standard_normal((B, D)).astype(np.float32),
                rng.standard_normal((B, D)).astype(np.float32))

    def build(alive):
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        paddle.seed(0)
        return Engine(_toy_model(D), mesh, lr=0.05, clip_norm=None,
                      guard=policy)

    return build, data_fn


def _numeric_policy(recover, action):
    """Recovery on = the requested policy; recovery off = WARN (detection
    stays armed, the anomalous update is applied — an unguarded run)."""
    from paddle_tpu.framework.numeric_guard import GuardPolicy

    kw = dict(warmup_steps=3, spike_factor=50.0)
    return (GuardPolicy(action=action, **kw) if recover
            else GuardPolicy(action="warn", **kw))


def drill_nan_grad(recover: bool):
    """A NaN gradient at one step. Recovery = the health word (computed
    on-device, one scalar) flags PT-NUM-001, the in-graph zero-apply skips
    the update (step counter advances, optimizer moments untouched), and
    training continues finite. Without recovery the NaN lands in the
    optimizer state and every later loss is NaN."""
    import warnings

    import numpy as np

    from paddle_tpu.distributed.resilience import (FaultPlan, FaultSpec,
                                                   ResilientTrainer)

    build, data_fn = _guarded_fixture(_numeric_policy(recover, "skip_step"))
    plan = FaultPlan(seed=13, specs=[
        FaultSpec("numeric.step", "nan_grad", at=3, count=1)])
    with tempfile.TemporaryDirectory() as tmp:
        trainer = ResilientTrainer(build, tmp, save_every=100,
                                   async_save=False)
        with plan, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out = trainer.fit(data_fn, 8)
    if not plan.log:
        return False, "nan_grad fault never fired"
    final = out["losses"][8]
    if not recover:
        if np.isfinite(final):
            return True, ("unexpected: NaN grads absorbed without the "
                          "skip policy")
        return False, ("no guard action: NaN reached the optimizer state, "
                       f"final loss {final}")
    if out["numeric_skips"] != [4]:
        return False, f"expected skip at step 4, got {out['numeric_skips']}"
    if not np.isfinite(final):
        return False, f"skip failed to protect state: final loss {final}"
    return True, (f"PT-NUM-001 at step 4 skipped in-graph, moments "
                  f"untouched, final loss {final:.6f} finite")


def drill_loss_spike(recover: bool):
    """A 1024x loss spike mid-run. Recovery = the EMA/deviation detector
    flags PT-NUM-004 and the ROLLBACK policy restores the last committed
    ring entry, deterministically re-seeds and replays — the final loss
    must MATCH the uninterrupted seeded run. Without recovery the spiked
    gradients wreck the trajectory."""
    import warnings

    import numpy as np

    from paddle_tpu.distributed.resilience import (FaultPlan, FaultSpec,
                                                   ResilientTrainer)

    build, data_fn = _guarded_fixture(_numeric_policy(True, "rollback"))
    with tempfile.TemporaryDirectory() as tmp:
        ref = ResilientTrainer(build, os.path.join(tmp, "ref"),
                               save_every=100, async_save=False
                               ).fit(data_fn, 8)
        ref_final = ref["losses"][8]

        build2, _ = _guarded_fixture(_numeric_policy(recover, "rollback"))
        plan = FaultPlan(seed=13, specs=[
            FaultSpec("numeric.step", "loss_spike", at=5, count=1)])
        trainer = ResilientTrainer(build2, os.path.join(tmp, "job"),
                                   save_every=2, async_save=False)
        with plan, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out = trainer.fit(data_fn, 8)
        if not plan.log:
            return False, "loss_spike fault never fired"
        final = out["losses"][8]
        if not recover:
            if np.allclose(final, ref_final, rtol=1e-3):
                return True, ("unexpected: 1024x spiked step left the "
                              "trajectory intact")
            return False, (f"no rollback: spiked update applied, final "
                           f"{final:.4f} vs uninterrupted {ref_final:.4f}")
        if out["numeric_rollbacks"] < 1:
            return False, "spike never triggered a rollback"
        if not np.allclose(final, ref_final, rtol=1e-3):
            return False, (f"post-rollback trajectory diverged: {final} vs "
                           f"uninterrupted {ref_final}")
        return True, (f"PT-NUM-004 at step 6, rolled back to "
                      f"{out['rollback_at'][0]}, replay matches "
                      f"uninterrupted ({final:.6f})")


def drill_poison_batch(recover: bool):
    """A seeded NaN-poisoned batch from the data pipeline. Recovery = skip
    the step AND capture the batch to ckpt_dir/badbatch/ where
    tools/replay_batch.py reproduces the anomaly in isolation. Without
    recovery the poisoned batch NaNs the run."""
    import warnings

    import numpy as np

    from paddle_tpu.distributed.resilience import (FaultPlan, FaultSpec,
                                                   ResilientTrainer)

    build, data_fn = _guarded_fixture(_numeric_policy(recover, "skip_step"))
    plan = FaultPlan(seed=5, specs=[
        FaultSpec("data.batch", "poison_batch", at=4, count=1, arg=4)])
    with tempfile.TemporaryDirectory() as tmp:
        trainer = ResilientTrainer(build, tmp, save_every=100,
                                   async_save=False)
        with plan, warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out = trainer.fit(data_fn, 8)
        if not plan.log:
            return False, "poison_batch fault never fired"
        final = out["losses"][8]
        if not recover:
            if np.isfinite(final):
                return True, "unexpected: poisoned batch absorbed under warn"
            return False, f"no guard action: poisoned batch NaN'd the run"
        if not np.isfinite(final):
            return False, f"skip failed: final loss {final}"
        if out["numeric_skips"] != [5]:
            return False, f"expected skip at step 5, got {out['numeric_skips']}"
        from paddle_tpu.framework.numeric_guard import BadBatchRecorder

        rec = BadBatchRecorder(os.path.join(tmp, "badbatch"))
        if rec.steps() != [5]:
            return False, f"bad batch not captured: {rec.steps()}"
        meta, arrays = rec.load(5)
        if not np.isnan(arrays["input_ids"]).any() and \
                not np.isnan(arrays["labels"]).any():
            return False, "captured batch carries no NaN"
        return True, (f"poisoned batch skipped at step 5, captured "
                      f"({'|'.join(meta['bits'])}) for replay_batch.py")


# ---------------------------------------------------------------------------
# serving supervisor drills: crash, stall, overload (docs/SERVING.md)
# ---------------------------------------------------------------------------

def _crash_wave():
    """The crash drill wave: a short greedy request whose full-page prompt
    registers in the radix cache, a long seeded sampled request, and a
    repeat of the first prompt — admitted AFTER the first finished, so it
    takes the full-prompt-hit COW path and is mid-decode PAST the
    copy-on-write divergence point when the kill lands. Params only;
    Request objects are built fresh per run."""
    import numpy as np

    cfg, _ = _serving_model()
    rng = np.random.default_rng(17)
    pa = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)   # 1 full page
    pb = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    return [
        dict(prompt_ids=pa, max_new_tokens=4, seed=50),
        dict(prompt_ids=pb, max_new_tokens=12, temperature=0.9, seed=77),
        dict(prompt_ids=pa, max_new_tokens=8, seed=50),           # COW hit
    ]


def _crash_build():
    _, m = _serving_model()
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                    block_size=2, prefix_cache=True)


def _crash_refs():
    """Uninterrupted supervisor reference streams (computed once, cached —
    both recovery modes and the stall drill compare against them)."""
    if "crash_refs" not in _SERVING:
        from paddle_tpu.inference.serving import Request, ServingSupervisor

        with tempfile.TemporaryDirectory() as tmp:
            sup = ServingSupervisor(_crash_build,
                                    os.path.join(tmp, "ref.jrnl"))
            reqs = [Request(**kw) for kw in _crash_wave()]
            for r in reqs:
                sup.submit(r)
            sup.run_until_done(max_steps=500)
            sup.close()
        _SERVING["crash_refs"] = [list(r.tokens) for r in reqs]
    return _SERVING["crash_refs"]


def drill_serving_crash(recover: bool):
    """The engine process dies mid-decode (FaultPlan ``serving.step`` kill).
    Recovery = the ServingSupervisor rebuilds a fresh engine (new block
    pool, empty radix cache) and replays every journaled unfinished request
    — token streams BIT-IDENTICAL to the uninterrupted run (greedy, seeded,
    and across the COW divergence point). Without the supervisor's journal
    the crash loses every in-flight request."""
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.serving import Request, ServingSupervisor

    refs = _crash_refs()
    # at=3: the fourth engine step — the seeded request AND the COW-hit
    # repeat are both mid-decode (the repeat already past its COW point)
    plan = FaultPlan(seed=3, specs=[
        FaultSpec("serving.step", "kill", at=3, count=1)])
    with tempfile.TemporaryDirectory() as tmp:
        sup = ServingSupervisor(_crash_build, os.path.join(tmp, "j.jrnl"),
                                max_recoveries=2 if recover else 0)
        reqs = [Request(**kw) for kw in _crash_wave()]
        try:
            with plan:
                for r in reqs:
                    sup.submit(r)
                sup.run_until_done(max_steps=500)
        except Exception as e:
            if recover:
                return False, f"supervisor did not absorb the crash: {e!r}"
            lost = [r.rid for r in reqs if not r.done]
            if not lost:
                return True, "unexpected: crash raised but no request lost"
            return False, (f"no journal/supervisor: engine crash lost "
                           f"{len(lost)} in-flight request(s) {lost}")
        finally:
            sup.close()
        if not plan.log:
            return False, "serving.step kill never fired"
        if not recover:
            return True, "unexpected: crash absorbed without recovery"
        if sup.recoveries < 1:
            return False, "crash never triggered a rebuild"
        streams = [list(r.tokens) for r in reqs]
        if streams != refs:
            bad = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
            return False, (f"recovered stream(s) {bad} diverged from the "
                           "uninterrupted run")
        return True, (f"PT-SRV-001: crash at {plan.log[0][1]}, rebuilt + "
                      f"replayed {sup.stats['replayed_requests']} request(s) "
                      f"in {sup.stats['recovery_s']:.2f}s, all 3 streams "
                      "bit-identical (incl. COW + seeded sampling)")


def _mesh_model():
    """tp=4-capable tiny llama (4 kv heads so both tp=4 and the degraded
    tp=2 divide the head counts) — separate from ``_serving_model`` whose
    2 kv heads cap it at tp=2."""
    if "mesh_model" not in _SERVING:
        import paddle_tpu as paddle
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(11)
        cfg = LlamaConfig.tiny(num_hidden_layers=1, num_key_value_heads=4)
        _SERVING["mesh_model"] = (cfg, LlamaForCausalLM(cfg))
    return _SERVING["mesh_model"]


def _mesh_wave():
    """Greedy full-page prompt + long seeded sampled request — the
    byte-identity claim must survive the reshard in BOTH decode modes."""
    import numpy as np

    cfg, _ = _mesh_model()
    rng = np.random.default_rng(21)
    pa = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    return [
        dict(prompt_ids=pa, max_new_tokens=6, seed=40),
        dict(prompt_ids=pb, max_new_tokens=10, temperature=0.9, seed=71),
    ]


def _mesh_build(mesh_tp=4):
    """Width-aware factory: the elastic supervisor rebuilds through it at
    the surviving width (mesh_tp=None = fall back to unsharded)."""
    _, m = _mesh_model()
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              MeshConfig, PrefixCacheConfig)

    mesh = None if mesh_tp is None else MeshConfig(tp=int(mesh_tp))
    return ContinuousBatchingEngine(
        m, max_batch=2, max_len=32, page_size=8, block_size=2,
        prefix_cache=PrefixCacheConfig(extra_blocks=4), mesh=mesh)


def _mesh_refs():
    """Uninterrupted tp=4 supervisor reference streams (cached)."""
    if "mesh_refs" not in _SERVING:
        from paddle_tpu.inference.serving import Request, ServingSupervisor

        with tempfile.TemporaryDirectory() as tmp:
            sup = ServingSupervisor(_mesh_build,
                                    os.path.join(tmp, "ref.jrnl"))
            reqs = [Request(**kw) for kw in _mesh_wave()]
            for r in reqs:
                sup.submit(r)
            sup.run_until_done(max_steps=500)
            sup.close()
        _SERVING["mesh_refs"] = [list(r.tokens) for r in reqs]
    return _SERVING["mesh_refs"]


def drill_mesh_device_loss(recover: bool):
    """A tp=4 engine loses 2 of its devices mid-decode (FaultPlan
    ``device.loss`` -> MeshDegraded / PT-SRV-008). Recovery = the elastic
    ServingSupervisor harvests the column shards host-side, rebuilds at
    the widest surviving width (tp=2), re-splits the same bytes, and
    replays every journaled request — streams BIT-IDENTICAL to the
    uninterrupted tp=4 run (greedy + seeded; the column-parallel
    all_gather-only contract makes the widths interchangeable). Without
    the degrade path (elastic=False) the typed signal escapes and every
    in-flight request is lost with the device group."""
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.serving import Request, ServingSupervisor

    refs = _mesh_refs()
    # at=1: the SECOND engine step — step 1 admits + prefills, so the loss
    # lands with both requests mid-decode (the fused engine runs each wave
    # to its next completion event, so the whole drill is only ~3 steps)
    plan = FaultPlan(seed=5, specs=[
        FaultSpec("device.loss", "lose", at=1, count=1, arg=2)])
    with tempfile.TemporaryDirectory() as tmp:
        sup = ServingSupervisor(_mesh_build, os.path.join(tmp, "j.jrnl"),
                                elastic=recover)
        reqs = [Request(**kw) for kw in _mesh_wave()]
        try:
            with plan:
                for r in reqs:
                    sup.submit(r)
                sup.run_until_done(max_steps=500)
        except Exception as e:
            if recover:
                return False, f"supervisor did not absorb the degrade: {e!r}"
            lost = [r.rid for r in reqs if not r.done]
            if not lost:
                return True, "unexpected: degrade raised but no request lost"
            return False, (f"no elastic degrade path: losing 2 devices lost "
                           f"{len(lost)} in-flight request(s) {lost}")
        finally:
            sup.close()
        if not plan.log:
            return False, "device.loss never fired"
        if not recover:
            return True, "unexpected: degrade absorbed with elastic off"
        if sup.stats["mesh_reshards"] < 1:
            return False, "device loss never triggered a reshard"
        tp = (int(sup.engine.mesh.tp)
              if getattr(sup.engine, "mesh", None) is not None else 1)
        if tp != 2:
            return False, (f"expected the widest surviving width tp=2, "
                           f"engine is at tp={tp}")
        streams = [list(r.tokens) for r in reqs]
        if streams != refs:
            bad = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
            return False, (f"resharded stream(s) {bad} diverged from the "
                           "uninterrupted tp=4 run")
        return True, (f"PT-SRV-008: lost 2/4 devices at {plan.log[0][1]}, "
                      f"resharded tp=4->2 + replayed "
                      f"{sup.stats['replayed_requests']} request(s) in "
                      f"{sup.stats['recovery_s']:.2f}s, streams "
                      "bit-identical (greedy + seeded)")


def drill_serving_stall(recover: bool):
    """One engine step hangs (FaultPlan ``serving.stall``). Recovery = the
    threaded StepWatchdog flags PT-SRV-002 while the step is stuck and the
    supervisor rebuilds-from-journal; streams stay bit-identical. Without
    the watchdog the stall silently blows the per-step latency SLO.

    Runs on the engine without a prefix cache, WARMED with an identical wave
    first so every armed step reuses compiled programs — a compile-heavy
    step is indistinguishable from a stall, which is exactly why the
    supervisor warms before arming (and graces steps after a rebuild)."""
    import time as _t

    import numpy as np

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request, ServingSupervisor)

    BUDGET, STALL = 0.6, 1.5
    cfg, m = _serving_model()
    rng = np.random.default_rng(29)
    ps = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
          for _ in range(2)]

    def build():
        return ContinuousBatchingEngine(m, max_batch=2, max_len=32,
                                        page_size=8, block_size=2)

    def wave(sup):
        reqs = [Request(p, max_new_tokens=8, seed=60 + i)
                for i, p in enumerate(ps)]
        for r in reqs:
            sup.submit(r)
        return reqs

    plan = FaultPlan(seed=4, specs=[
        FaultSpec("serving.stall", "stall", at=2, count=1, arg=STALL)])
    with tempfile.TemporaryDirectory() as tmp:
        sup = ServingSupervisor(build, os.path.join(tmp, "j.jrnl"))
        warm_reqs = wave(sup)              # identical wave: warms every
        sup.run_until_done(max_steps=200)  # program the armed wave will run
        refs = [list(r.tokens) for r in warm_reqs]
        if recover:
            sup.set_step_budget(BUDGET)
        reqs = wave(sup)
        step_s = []
        try:
            import warnings

            with plan, warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                while sup.has_work():
                    t0 = _t.perf_counter()
                    sup.step()
                    step_s.append(_t.perf_counter() - t0)
        finally:
            sup.close()
        if not plan.log:
            return False, "serving.stall never fired"
        streams = [list(r.tokens) for r in reqs]
        if not recover:
            worst = max(step_s)
            if worst <= BUDGET:
                return True, "unexpected: stall absorbed under budget"
            return False, (f"no watchdog: a step silently took {worst:.2f}s "
                           f"(budget {BUDGET}s) — stall undetected, SLO "
                           "violated")
        codes = [c for c, _ in sup.events]
        if "PT-SRV-002" not in codes:
            return False, f"watchdog never flagged the stall (events {codes})"
        if streams != refs:
            return False, "post-rebuild streams diverged"
        return True, (f"PT-SRV-002: stall flagged mid-hang, rebuilt in "
                      f"{sup.stats['recovery_s']:.2f}s, streams bit-identical")


def drill_serving_overload_shed(recover: bool):
    """An infeasible-deadline request arrives while the engine is busy.
    Recovery = deadline-feasibility shedding refuses it AT SUBMIT with a
    typed RequestShed (PT-SRV-003) — before it occupies a slot or queue
    time — and the running requests' streams are byte-identical to a run
    without it. Without shedding it queues, burns its wait, and dies by
    deadline eviction after the fact."""
    import numpy as np

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request, RequestShed)

    cfg, m = _serving_model()
    rng = np.random.default_rng(23)
    ps = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
          for _ in range(2)]

    def survivors_wave(e):
        reqs = [Request(p, max_new_tokens=8, seed=100 + i)
                for i, p in enumerate(ps)]
        for r in reqs:
            e.add_request(r)
        return reqs

    def make():
        e = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                     block_size=2,
                                     shed_infeasible=recover)
        warm = Request(np.asarray([4, 5], np.int32), max_new_tokens=2)
        e.add_request(warm)
        e.run_until_done()          # compiles + measures the decode rate
        return e

    if "shed_refs" not in _SERVING:
        eng0 = make()
        reqs0 = survivors_wave(eng0)
        eng0.run_until_done(max_steps=300)
        _SERVING["shed_refs"] = [list(r.tokens) for r in reqs0]
    refs = _SERVING["shed_refs"]

    eng = make()
    survivors = survivors_wave(eng)
    eng.step()                       # survivors admitted and decoding
    doomed = Request(ps[0], max_new_tokens=16, deadline_s=1e-3)
    shed = False
    try:
        eng.add_request(doomed)
    except RequestShed:
        shed = True
    eng.run_until_done(max_steps=300)
    streams = [list(r.tokens) for r in survivors]
    if not recover:
        if shed:
            return True, "unexpected: shed fired with shedding disabled"
        if not doomed.failed or "deadline" not in (doomed.error or ""):
            return False, ("no shedding: infeasible request neither shed "
                           "nor deadline-evicted — it just hogged the queue")
        return False, ("no shedding: infeasible request queued and died by "
                       f"deadline eviction after the fact ({doomed.error})")
    if not shed:
        return False, "infeasible request was not shed at submit"
    if doomed._n_out != 0 or doomed.rid in [r.rid for r in eng._queue]:
        return False, "shed request occupied engine state"
    if streams != refs:
        return False, "survivors' streams changed by the shed request"
    return True, (f"PT-SRV-003: infeasible deadline shed at submit "
                  f"({eng.stats['shed']} shed), survivors byte-identical")


def drill_kv_migration_corruption(recover: bool):
    """One migrated KV chain's page bytes are flipped in transit between
    the prefill and decode tiers (FaultPlan ``serving.kv_transfer``
    bitflip — docs/SERVING.md "Disaggregated tiers"). Recovery = the
    codec's per-page crc32 refuses the splice with a typed
    ``KVChainCorrupt`` (PT-SRV-007) and the decode replica re-runs prefill
    from the journaled admit — every stream byte-identical to a
    single-replica run (greedy and seeded). Without verification
    (``KVChainCodec(verify_crc=False)``: what a checksum-less transfer
    does) the corrupt pages are spliced into the decode pool and the
    migrated request's stream silently diverges."""
    import tempfile as _tempfile

    import numpy as np

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.disagg import KVChainCodec, TieredRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request)

    cfg, m = _serving_model()
    rng = np.random.default_rng(61)
    kws = []
    for i in range(4):
        p = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        kw = dict(prompt_ids=p, max_new_tokens=8, seed=600 + i)
        if i % 2 == 1:
            kw.update(temperature=0.9)
        kws.append(kw)

    def build():
        return ContinuousBatchingEngine(m, max_batch=2, max_len=32,
                                        page_size=8, block_size=2,
                                        prefix_cache=True)

    if "disagg_refs" not in _SERVING:
        eng = build()
        reqs0 = [Request(**kw) for kw in kws]
        for r in reqs0:
            eng.add_request(r)
        eng.run_until_done(max_steps=500)
        _SERVING["disagg_refs"] = [list(r.tokens) for r in reqs0]
    refs = _SERVING["disagg_refs"]

    plan = FaultPlan(seed=3, specs=[
        FaultSpec("serving.kv_transfer", "bitflip", at=0, count=1, arg=256)])
    with _tempfile.TemporaryDirectory() as tmp:
        tiered = TieredRouter(build, build, tmp, num_prefill=1,
                              num_decode=1,
                              codec=KVChainCodec(verify_crc=recover))
        reqs = [Request(**kw) for kw in kws]
        try:
            with plan:
                for r in reqs:
                    tiered.submit(r)
                tiered.run_until_done(max_steps=2000)
        finally:
            tiered.close()
    if not plan.log:
        return False, "serving.kv_transfer bitflip never fired"
    streams = [list(r.tokens) for r in reqs]
    wrong = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
    if not recover:
        if not wrong:
            return True, ("unexpected: 256 flipped bits spliced without "
                          "changing any stream")
        return False, ("no chain verification: corrupt pages spliced into "
                       f"the decode pool — stream(s) {wrong} silently "
                       "diverged from the single-replica run")
    if tiered.stats["migration_corrupt"] < 1:
        return False, "corruption never detected at import"
    codes = [c for c, _ in tiered.events]
    if "PT-SRV-007" not in codes:
        return False, f"no typed PT-SRV-007 rejection (events {codes})"
    if tiered.stats["migration_reprefill"] < 1:
        return False, "decode side never re-ran the corrupted prefill"
    if wrong:
        return False, (f"stream(s) {wrong} diverged despite the re-run "
                       "(recovery broken)")
    # int8 block-format arm: a bitflip in the QUANTIZED page bytes of a
    # PTKV1 chain must still raise the typed PT-SRV-007 (the per-page crc
    # covers the int8 bytes exactly as stored; the dequant scales ride the
    # digest-protected header)
    from paddle_tpu.inference.disagg import KVChainCorrupt, KVChainCodec

    src = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                   block_size=2, prefix_cache=True,
                                   kv_cache="int8")
    req8 = Request(rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32),
                   max_new_tokens=16)
    src.add_request(req8)
    src.step()
    codec = KVChainCodec()
    art = codec.export_chain(src, req8.rid)
    flipped = bytearray(art)
    flipped[-5] ^= 0x20                      # a quantized payload byte
    dst = ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                   block_size=2, prefix_cache=True,
                                   kv_cache="int8")
    try:
        codec.import_chain(dst, bytes(flipped))
        return False, ("int8 chain: flipped quantized byte spliced "
                       "without a PT-SRV-007 rejection")
    except KVChainCorrupt:
        pass
    src.withdraw_active(req8.rid)
    twin = codec.import_chain(dst, art)      # clean splice must still work
    dst.run_until_done(max_steps=200)
    if len(twin.tokens) != 16:
        return False, ("int8 chain: clean splice did not resume decode "
                       f"({len(twin.tokens)}/16 tokens)")
    return True, ("PT-SRV-007: flipped page refused at import (per-page "
                  "crc32), prefill re-run on the decode replica, all "
                  f"{len(reqs)} streams bit-identical "
                  f"({tiered.stats['migrations']} clean migration(s) "
                  "alongside); int8 chain bitflip equally refused and the "
                  "clean int8 splice resumed decode")


def drill_spec_decode_divergence(recover: bool):
    """Speculative multi-token decoding with its in-graph verification
    DISABLED (docs/SERVING.md "Speculative decode"). Recovery = the
    normal draft -> verify -> accept/rollback pipeline: greedy streams are
    byte-identical to the non-speculative mega-step (drafts only change
    how many tokens a dispatch emits, never which), with acceptance > 0 on
    the repetitive workload. Without verification
    (``SpecConfig(_unsafe_accept_all=True)``: what trusting a drafter
    blindly does) every draft is emitted as-is and the greedy streams
    silently diverge — the failure mode the verify program exists to
    prevent."""
    import numpy as np

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request, SpecConfig)

    cfg, m = _serving_model()
    rng = np.random.default_rng(73)
    motif = rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
    prompts = [np.tile(motif, 6),                       # repetitive: the
               np.tile(motif, 6),                       # drafter's food
               rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32),
               rng.integers(0, cfg.vocab_size, (14,)).astype(np.int32)]
    new_toks = [24, 16, 12, 12]

    def wave(eng):
        reqs = [Request(p, max_new_tokens=k)
                for p, k in zip(prompts, new_toks)]
        for r in reqs:
            eng.add_request(r)
        eng.run_until_done(max_steps=800)
        return [list(r.tokens) for r in reqs]

    if "spec_refs" not in _SERVING:
        _SERVING["spec_refs"] = wave(ContinuousBatchingEngine(
            m, max_batch=4, max_len=64, page_size=8, block_size=2))
    refs = _SERVING["spec_refs"]
    spec = SpecConfig(k=3, _unsafe_accept_all=not recover)
    eng = ContinuousBatchingEngine(m, max_batch=4, max_len=64, page_size=8,
                                   block_size=2, speculative=spec)
    streams = wave(eng)
    wrong = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
    if not recover:
        if not wrong:
            return True, ("unexpected: accept-all emitted every draft yet "
                          "no stream diverged")
        return False, ("verification disabled (accept-all): draft tokens "
                       f"streamed unchecked — stream(s) {wrong} silently "
                       "diverged from the non-speculative mega-step")
    if wrong:
        return False, (f"stream(s) {wrong} diverged WITH verification on "
                       "(greedy byte-identity broken)")
    if eng.stats["spec_accepted"] < 1:
        return False, ("no draft accepted on the repetitive workload — "
                       "the drafter/verify pipeline is not speculating")
    return True, ("greedy streams byte-identical to the non-speculative "
                  f"mega-step with {eng.stats['spec_accepted']}/"
                  f"{eng.stats['spec_proposed']} drafts accepted over "
                  f"{eng.stats['spec_steps']} verify dispatches")


def _fleet_build():
    _, m = _serving_model()
    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(m, max_batch=2, max_len=32, page_size=8,
                                    block_size=2)


def _fleet_wave_kwargs():
    """Mixed fleet wave: greedy and seeded-sampled requests (params only;
    Request objects are built fresh per run)."""
    import numpy as np

    cfg, _ = _serving_model()
    rng = np.random.default_rng(41)
    kws = []
    for i in range(6):
        p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        kw = dict(prompt_ids=p, max_new_tokens=8, seed=200 + i)
        if i % 3 == 2:
            kw.update(temperature=0.9)
        kws.append(kw)
    return kws


def _fleet_refs():
    """Uninterrupted single-engine reference streams — per-request
    determinism means any fleet placement must reproduce them exactly."""
    if "fleet_refs" not in _SERVING:
        from paddle_tpu.inference.serving import Request

        eng = _fleet_build()
        reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
        for r in reqs:
            eng.add_request(r)
        eng.run_until_done(max_steps=500)
        _SERVING["fleet_refs"] = [list(r.tokens) for r in reqs]
    return _SERVING["fleet_refs"]


def drill_fleet_replica_kill(recover: bool):
    """One of three replicas dies mid-traffic (FaultPlan
    ``fleet.replica_kill``). Recovery = the FleetRouter reads the dead
    replica's ON-DISK journal, re-admits its unfinished requests on
    survivors and catches them up to the delivered high-water marks —
    every stream byte-identical to an uninterrupted run (PT-FLT-001).
    Without failover the dead replica's in-flight requests are lost."""
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.fleet import FleetRouter
    from paddle_tpu.inference.serving import Request

    refs = _fleet_refs()
    plan = FaultPlan(seed=5, specs=[
        FaultSpec("fleet.replica_kill", "kill", at=2, count=1,
                  match="replica:0:")])
    with tempfile.TemporaryDirectory() as tmp:
        fleet = FleetRouter(_fleet_build, tmp, num_replicas=3,
                            failover=recover)
        reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
        try:
            with plan:
                for r in reqs:
                    fleet.submit(r)
                fleet.run_until_done(max_steps=500)
        finally:
            fleet.close()
    if not plan.log:
        return False, "fleet.replica_kill never fired"
    if fleet.stats["replica_deaths"] != 1:
        return False, (f"expected exactly one replica death, saw "
                       f"{fleet.stats['replica_deaths']}")
    lost = [r.rid for r in reqs if r.failed or not r.done]
    if not recover:
        if not lost:
            return True, "unexpected: replica death lost nothing"
        return False, (f"no failover: replica 0 died and lost {len(lost)} "
                       f"in-flight request(s) {lost}")
    if lost:
        return False, f"failover left request(s) {lost} failed/unfinished"
    streams = [list(r.tokens) for r in reqs]
    if streams != refs:
        bad = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
        return False, (f"failed-over stream(s) {bad} diverged from the "
                       "uninterrupted run")
    return True, (f"PT-FLT-001: replica 0 killed mid-traffic, "
                  f"{fleet.stats['failover_requests']} journaled request(s) "
                  f"re-admitted on survivors in "
                  f"{fleet.stats['failover_s']:.2f}s, all "
                  f"{len(reqs)} streams bit-identical (greedy + seeded)")


def drill_fleet_proc_kill(recover: bool):
    """One of two replica WORKER PROCESSES takes a real SIGKILL mid-decode
    (the ``fleet.proc_kill`` site fires inside the driver-side proxy,
    which kills the actual pid — inference/procfleet). Recovery = the
    router reads the dead PROCESS's on-disk journal, re-admits its
    unfinished requests on the surviving worker process and catches them
    up to the delivered high-water marks — every stream byte-identical to
    an uninterrupted run (PT-FLT-001 over the PT-PROC transport). Without
    failover the dead process's in-flight requests are lost."""
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.procfleet import (ProcFleetConfig,
                                                ProcFleetRouter)
    from paddle_tpu.inference.serving import Request

    refs = _fleet_refs()
    plan = FaultPlan(seed=5, specs=[
        FaultSpec("fleet.proc_kill", "kill", at=2, count=1,
                  match="replica:0:")])
    # the worker factory rebuilds the drill's model in the child with the
    # SAME seed (_serving_model seeds 11): byte-identity across processes
    # needs bit-identical weights per replica
    proc = ProcFleetConfig(
        factory="paddle_tpu.inference.procfleet.presets:tiny_llama_engine",
        factory_kwargs={"seed": 11}, env={"JAX_PLATFORMS": "cpu"})
    with tempfile.TemporaryDirectory() as tmp:
        fleet = ProcFleetRouter(proc, tmp, num_replicas=2,
                                failover=recover)
        pid0 = fleet.replicas[0].sup.worker_pid
        reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
        try:
            with plan:
                for r in reqs:
                    fleet.submit(r)
                fleet.run_until_done(max_steps=500)
        finally:
            fleet.close()
    if not plan.log:
        return False, "fleet.proc_kill never fired"
    try:
        os.kill(pid0, 0)
        return False, f"worker pid {pid0} survived its SIGKILL"
    except ProcessLookupError:
        pass
    if fleet.stats["replica_deaths"] != 1:
        return False, (f"expected exactly one process death, saw "
                       f"{fleet.stats['replica_deaths']}")
    lost = [r.rid for r in reqs if r.failed or not r.done]
    if not recover:
        if not lost:
            return True, "unexpected: process death lost nothing"
        return False, (f"no failover: worker process 0 was SIGKILL'd and "
                       f"lost {len(lost)} in-flight request(s) {lost}")
    if lost:
        return False, f"failover left request(s) {lost} failed/unfinished"
    streams = [list(r.tokens) for r in reqs]
    if streams != refs:
        bad = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
        return False, (f"failed-over stream(s) {bad} diverged from the "
                       "uninterrupted run")
    return True, (f"PT-PROC/PT-FLT-001: worker process {pid0} SIGKILL'd "
                  f"mid-decode, {fleet.stats['failover_requests']} "
                  "journaled request(s) re-admitted on the surviving "
                  f"process in {fleet.stats['failover_s']:.2f}s, all "
                  f"{len(reqs)} streams bit-identical (greedy + seeded)")


# ---------------------------------------------------------------------------
# drills: the transport seam — flaky wire under KV migration, slow peer
# ---------------------------------------------------------------------------

def _net_cfg(factory="tiny_llama_engine", fkw=None, **kw):
    """Loopback-transport fleet config for the net.* drills (workers are
    threads in THIS process — the chaos plan and the drill share one
    interpreter, and there is no process spawn in the latency budget)."""
    from paddle_tpu.inference.procfleet import ProcFleetConfig

    return ProcFleetConfig(
        factory=f"paddle_tpu.inference.procfleet.presets:{factory}",
        factory_kwargs={"seed": 11, **(fkw or {})},
        transport="loopback", **kw)


def _net_flat_refs():
    """Fault-free loopback FLAT fleet run (cached). Doubles as the jit
    warmup for the armed runs — loopback workers compile in this very
    process, and a cold compile under a tight chaos op-timeout would
    read as a wedged peer — and pins the loopback placement
    byte-identical to the single-engine reference streams."""
    if "net_flat" not in _SERVING:
        from paddle_tpu.inference.procfleet import ProcFleetRouter
        from paddle_tpu.inference.serving import Request

        refs = _fleet_refs()
        with tempfile.TemporaryDirectory() as tmp:
            fleet = ProcFleetRouter(_net_cfg(), tmp, num_replicas=2)
            reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
            try:
                for r in reqs:
                    fleet.submit(r)
                fleet.run_until_done(max_steps=500)
            finally:
                fleet.close()
        streams = [list(r.tokens) for r in reqs]
        if any(r.failed or not r.done for r in reqs) or streams != refs:
            raise RuntimeError("clean loopback fleet run did not reproduce "
                               "the reference streams")
        _SERVING["net_flat"] = refs
    return _SERVING["net_flat"]


def _net_tiered_refs():
    """Fault-free loopback TIERED run (cached): warms the prefill ->
    decode migration path (export/import/splice programs) on top of the
    flat warmup and pins it byte-identical to the same reference."""
    if "net_tiered" not in _SERVING:
        from paddle_tpu.inference.procfleet import ProcTieredRouter
        from paddle_tpu.inference.serving import Request

        refs = _net_flat_refs()
        with tempfile.TemporaryDirectory() as tmp:
            tiered = ProcTieredRouter(
                _net_cfg("tiny_llama_prefix_engine"),
                _net_cfg("tiny_llama_prefix_engine"),
                tmp, num_prefill=1, num_decode=2)
            reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
            try:
                for r in reqs:
                    tiered.submit(r)
                tiered.run_until_done(max_steps=500)
            finally:
                tiered.close()
        streams = [list(r.tokens) for r in reqs]
        if any(r.failed or not r.done for r in reqs) or streams != refs:
            raise RuntimeError("clean tiered loopback run did not reproduce "
                               "the reference streams")
        if tiered.stats["migrations"] < 1:
            raise RuntimeError("clean tiered run never migrated")
        _SERVING["net_tiered"] = refs
    return _SERVING["net_tiered"]


def drill_net_flaky_migration(recover: bool):
    """The wire goes flaky exactly under KV migration: a seeded plan
    DROPS one MIGRATE_IN frame outright and BITFLIPS the KV payload of
    another on ``net.send`` (the chaos transport re-frames after the
    flip, so the frame CRC is VALID over the damaged bytes — only the
    end-to-end per-page chain crc32 can catch it). Recovery = the
    transport seam absorbs both: the dropped splice times out CLEANLY
    (peer alive — no kill) and is hedged onto the next-least-loaded
    decode replica under a stable idempotence key, the bitflipped one is
    refused typed (KVChainCorrupt -> retry elsewhere / reprefill
    fallback) — every stream byte-identical to the fault-free run. The
    control arm is a checksum-less transport (``verify_crc=False``)
    with hedging off: the damaged pages splice silently and the
    migrated streams diverge."""
    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.procfleet import ProcTieredRouter
    from paddle_tpu.inference.serving import Request

    refs = _net_tiered_refs()
    plan = FaultPlan(seed=7, specs=[
        FaultSpec("net.send", "drop", at=0, count=1, match="MIGRATE_IN"),
        FaultSpec("net.send", "bitflip", at=1, count=1, arg=64,
                  match="MIGRATE_IN")])

    def cfg():
        return _net_cfg("tiny_llama_prefix_engine", chaos=True,
                        op_timeout_s=5.0, hedge=recover,
                        verify_crc=recover)

    with tempfile.TemporaryDirectory() as tmp:
        tiered = ProcTieredRouter(cfg(), cfg(), tmp,
                                  num_prefill=1, num_decode=2)
        reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
        try:
            with plan:
                for r in reqs:
                    tiered.submit(r)
                tiered.run_until_done(max_steps=800)
        finally:
            tiered.close()
    fired = sorted({a for (_, _, a) in plan.log})
    if "drop" not in fired or "bitflip" not in fired:
        return False, f"net.send faults never fully fired (fired: {fired})"
    lost = [r.rid for r in reqs if r.failed or not r.done]
    streams = [list(r.tokens) for r in reqs]
    wrong = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
    s = tiered.stats
    if not recover:
        if s["migration_corrupt"]:
            return False, ("control arm still detected the flip "
                           "(verify_crc=False was not honored)")
        if lost:
            return False, (f"control arm lost request(s) {lost} — "
                           "expected SILENT corruption, not failure")
        if not wrong:
            return True, ("unexpected: checksum-less splice of flipped KV "
                          "pages changed no stream")
        return False, ("no chain verify + no hedging: damaged migration "
                       f"bytes spliced silently — stream(s) {wrong} "
                       "diverged from the fault-free run")
    if lost:
        return False, f"request(s) {lost} failed/unfinished under net faults"
    retries = sum(getattr(rep.sup, "transport_retries", 0)
                  for rep in tiered.replicas)
    recovered = s["migration_hedges"] + s["migration_corrupt"] + retries
    if recovered < 1:
        return False, (f"faults fired but no transport recovery engaged "
                       f"(stats {s})")
    if wrong:
        return False, (f"stream(s) {wrong} diverged despite typed refusal "
                       "+ hedged re-splice")
    return True, ("dropped + bitflipped MIGRATE_IN absorbed: "
                  f"{s['migration_hedges']} hedge(s), "
                  f"{s['migration_corrupt']} typed refusal(s), "
                  f"{s['migration_reprefill']} reprefill(s), "
                  f"{retries} clean timeout retry(s) — all {len(reqs)} "
                  "streams byte-identical to the fault-free run")


def drill_net_slow_peer(recover: bool):
    """One replica's wire turns SLOW-but-alive: a seeded plan stalls its
    next few replies (``net.recv`` stall — latency, not death; every
    reply still arrives, so kill-detection must NOT fire). Recovery =
    the per-peer circuit breaker: the first stalled reply blows the
    latency-EMA budget and trips CLOSED -> OPEN, the driver routes
    around the peer (typed BreakerOpen: submits fall through to
    survivors, step ticks are skipped) while HALF_OPEN probes riding the
    heartbeat re-test it off the driver path; once the weather passes a
    fast probe closes the breaker and the peer's streams finish —
    driver steps stay inside the latency budget and every stream is
    byte-identical. The control arm has no breaker: every stalled reply
    is eaten inline and driver step latency blows past the budget —
    the fleet-wide tail-latency incident the breaker exists to
    contain."""
    import time

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.procfleet import ProcFleetRouter
    from paddle_tpu.inference.serving import Request

    refs = _net_flat_refs()
    # loopback workers + heartbeats + driver share one interpreter, and
    # GIL/dispatch contention makes even fault-free ops take ~1-2s here:
    # the stall must TOWER over that baseline or the drill measures noise
    budget_s, stall_s = 3.0, 4.0
    plan = FaultPlan(seed=9, specs=[
        FaultSpec("net.recv", "stall", at=0, count=3, arg=stall_s,
                  match="replica:0@")])
    kw = dict(chaos=True, op_timeout_s=10.0)
    if recover:
        kw.update(heartbeat_s=0.5,
                  breaker={"fail_threshold": 99, "latency_s": 2.5,
                           "cooldown_s": 1.0, "ema_alpha": 1.0})
    with tempfile.TemporaryDirectory() as tmp:
        # max_batch=1: exactly one prefill and one decode program shape,
        # so every compile lands in the pre-roll — a mid-measurement
        # batch-shape recompile would read as a stalled driver step
        # (byte-identity is batch-invariant, so the refs still hold)
        fleet = ProcFleetRouter(_net_cfg(fkw={"max_batch": 1}, **kw), tmp,
                                num_replicas=2)
        rep0 = fleet.replicas[0].sup
        reqs = [Request(**wkw) for wkw in _fleet_wave_kwargs()]
        slow, worst = 0, 0.0
        try:
            for r in reqs:
                fleet.submit(r)
            # un-measured pre-roll: each armed fleet builds FRESH engines,
            # and their first steps pay jit compile (seconds) — latency the
            # drill must not confuse with the injected stalls. Roll until
            # EVERY replica's streams are advancing (compiles done on both
            # — a compile-slow step legitimately trips the breaker, which
            # then hides the un-compiled peer from the driver) and the
            # breaker has closed again.
            deadline = time.monotonic() + 120.0
            prev = [None] * len(fleet.replicas)
            adv = [0] * len(fleet.replicas)
            sampled = [r for r, wkw in zip(reqs, _fleet_wave_kwargs())
                       if wkw.get("temperature")]
            while time.monotonic() < deadline:
                fleet.step()
                # throttle: loopback workers and heartbeat probes share
                # this interpreter — a hot driver spin starves them on the
                # GIL and inflates EVERY op into breaker-budget territory,
                # burying the injected stalls in noise
                time.sleep(0.005)
                for i, rep in enumerate(fleet.replicas):
                    sig = rep.sup.progress()
                    if sig != prev[i]:
                        prev[i] = sig
                        adv[i] += 1
                # the sampled-decode program is a SECOND shape that only
                # compiles once a temperature>0 request reaches decode —
                # the pre-roll must cover it too
                if (min(adv) >= 4
                        and all(len(r.tokens) >= 1 for r in sampled)
                        and (not recover
                             or rep0.breaker_state() == "closed")):
                    break
            trips0 = rep0._breaker.trips if recover else 0
            with plan:
                while (any(not (r.done or r.failed) for r in reqs)
                       and time.monotonic() < deadline):
                    t0 = time.perf_counter()
                    fleet.step()
                    dt = time.perf_counter() - t0
                    worst = max(worst, dt)
                    slow += dt > budget_s
                    time.sleep(0.005)       # same GIL throttle, untimed
            trips = rep0._breaker.trips - trips0 if recover else 0
            state = rep0.breaker_state() if recover else "off"
        finally:
            fleet.close()
    stalls = sum(1 for (_, _, a) in plan.log if a == "stall")
    if not stalls:
        return False, "net.recv stall never fired"
    lost = [r.rid for r in reqs if r.failed or not r.done]
    if lost:
        return False, f"request(s) {lost} failed/unfinished under stalls"
    if fleet.stats["replica_deaths"]:
        return False, ("slow-but-alive peer was declared DEAD "
                       f"({fleet.stats['replica_deaths']} death(s)) — "
                       "latency must not be misread as a kill")
    streams = [list(r.tokens) for r in reqs]
    wrong = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
    if wrong:
        return False, f"stream(s) {wrong} diverged under stall injection"
    if not recover:
        if slow < 2:
            return True, ("unexpected: stalls absorbed without a breaker "
                          f"(worst step {worst:.2f}s)")
        return False, (f"no circuit breaker: {slow} driver step(s) blew "
                       f"past the {budget_s:.1f}s budget (worst "
                       f"{worst:.2f}s) eating stalled replies inline")
    if trips < 1:
        return False, "stalls never tripped the breaker"
    if slow > 1:
        return False, (f"breaker failed to insulate the driver: {slow} "
                       f"step(s) over budget (worst {worst:.2f}s)")
    return True, (f"slow peer contained: breaker tripped {trips}x (final "
                  f"state {state}), {stalls} stall(s) injected and at most "
                  f"one eaten inline before the trip ({slow} driver "
                  f"step(s) over budget, worst {worst:.2f}s), 0 replica "
                  f"deaths, all {len(reqs)} streams byte-identical")


def drill_fleet_drain(recover: bool):
    """Rolling restart of every replica under traffic (the ``fleet.drain``
    site drives the same path when planned). Recovery = graceful drain:
    stop admitting, migrate still-queued requests, finish in-flight slots,
    rebuild, rejoin — zero failed or duplicated tokens (PT-FLT-002).
    The control arm models a deployment that hard-restarts replicas
    without draining: in-flight work is lost."""
    from paddle_tpu.inference.fleet import FleetRouter
    from paddle_tpu.inference.serving import Request

    refs = _fleet_refs()
    with tempfile.TemporaryDirectory() as tmp:
        fleet = FleetRouter(_fleet_build, tmp, num_replicas=2,
                            graceful_drain=recover)
        reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
        try:
            for r in reqs:
                fleet.submit(r)
            fleet.step()                    # traffic in flight
            fleet.rolling_restart(max_steps=500)
            fleet.run_until_done(max_steps=500)
        finally:
            fleet.close()
    lost = [r.rid for r in reqs if r.failed or not r.done]
    if not recover:
        if not lost:
            return True, "unexpected: hard restart lost nothing"
        return False, (f"no graceful drain: hard replica restarts lost "
                       f"{len(lost)} in-flight request(s) {lost}")
    if lost:
        return False, f"rolling restart left request(s) {lost} failed"
    if fleet.stats["restarts"] < 2:
        return False, "replicas were never rebuilt"
    streams = [list(r.tokens) for r in reqs]
    if streams != refs:
        bad = [i for i, (s, f) in enumerate(zip(streams, refs)) if s != f]
        return False, (f"stream(s) {bad} diverged across the rolling "
                       "restart (lost or duplicated tokens)")
    return True, (f"PT-FLT-002: rolling restart under traffic — "
                  f"{fleet.stats['migrated']} queued request(s) migrated, "
                  f"{fleet.stats['restarts']} replicas rebuilt, zero "
                  "failed/duplicated tokens, streams bit-identical")


def drill_fleet_overload(recover: bool):
    """A sheddable low-priority flood hits every replica at once. Recovery
    = fleet brownout: once EVERY alive replica sits at depth, sheddable
    traffic is refused at submit with a typed ``RequestShed`` (PT-FLT-003)
    BEFORE queues saturate, so priority traffic still admits everywhere;
    the brownout exits hysteretically once pressure clears (PT-FLT-004).
    Without it the flood saturates every queue and priority traffic is
    refused with ``EngineSaturated``."""
    import numpy as np

    from paddle_tpu.inference.fleet import FleetConfig, FleetRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              EngineSaturated, Request,
                                              RequestShed)

    cfg, m = _serving_model()
    rng = np.random.default_rng(47)

    def build():
        return ContinuousBatchingEngine(m, max_batch=2, max_len=32,
                                        page_size=8, block_size=2,
                                        max_queue=2)

    config = FleetConfig(brownout_depth=(2 if recover else 10 ** 9),
                         brownout_enter_after=2, brownout_exit_after=2)
    with tempfile.TemporaryDirectory() as tmp:
        fleet = FleetRouter(build, tmp, num_replicas=3, config=config)
        shed = saturated = 0
        admitted = []
        try:
            for i in range(20):             # flood faster than service rate
                p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
                low = Request(p, max_new_tokens=8, seed=300 + i,
                              priority=Request.PRIORITY_LOW)
                try:
                    fleet.submit(low)
                    admitted.append(low)
                except RequestShed:
                    shed += 1
                except EngineSaturated:
                    saturated += 1
                if i % 3 == 2:              # service interleaves, but slower
                    fleet.step()            # than the flood arrives
            vip_refused = 0
            vips = []
            for i in range(3):              # priority traffic mid-flood
                p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
                vip = Request(p, max_new_tokens=4, seed=400 + i,
                              priority=Request.PRIORITY_HIGH)
                try:
                    fleet.submit(vip)
                    vips.append(vip)
                except (RequestShed, EngineSaturated):
                    vip_refused += 1
            fleet.run_until_done(max_steps=500)
        finally:
            fleet.close()
    if not recover:
        if not vip_refused:
            return True, ("unexpected: priority traffic admitted through "
                          "a saturating flood without fleet brownout")
        return False, (f"no fleet brownout: the flood saturated every "
                       f"replica ({saturated} EngineSaturated) and "
                       f"{vip_refused}/3 priority request(s) were refused")
    if fleet.stats["brownouts"] < 1:
        return False, "fleet brownout never entered under the flood"
    if not shed or fleet.stats["fleet_shed"] != shed:
        return False, f"flood was not shed at submit (shed={shed})"
    if saturated or vip_refused:
        return False, (f"brownout failed to protect admission "
                       f"(EngineSaturated={saturated}, vip_refused="
                       f"{vip_refused})")
    bad = [r.rid for r in vips + admitted if not r.done or r.failed]
    if bad:
        return False, f"admitted request(s) {bad} did not complete"
    if fleet._brownout_active:
        return False, "fleet brownout never exited after pressure cleared"
    return True, (f"PT-FLT-003/004: flood shed {shed}/20 at submit once "
                  f"every replica sat at depth, all 3 priority requests "
                  f"admitted + completed, zero EngineSaturated, brownout "
                  "exited hysteretically")


# ---------------------------------------------------------------------------
# drills: composed multi-site chaos + the full checkpoint-lifecycle arc
# ---------------------------------------------------------------------------

def drill_composed_chaos(recover: bool):
    """One seeded ComposedFaultPlan arms THREE fault sites at once against
    three subsystems running in parallel threads: the store daemon stalls
    past the client op deadline, a checkpoint shard is bitflipped on
    write, and a serving replica is killed mid-traffic. Recovery = each
    subsystem's own path absorbs its fault (PT-RETRY rides the stall,
    digest verification falls back to the replica copy, the fleet replays
    the dead replica's journal) — and the plan's per-spec RNG streams keep
    the injected damage byte-identical across runs no matter how the
    threads interleave. With recovery off (retries disabled, no replica
    copy, no failover) the same plan must bite."""
    import numpy as np

    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)
    from paddle_tpu.distributed.communication.store import TCPStore
    from paddle_tpu.distributed.resilience import (ComposedFaultPlan,
                                                   FaultSpec)
    from paddle_tpu.inference.fleet import FleetRouter
    from paddle_tpu.inference.serving import Request

    refs = _fleet_refs()
    w = np.arange(2048, dtype=np.float32)
    SITES = ("store.daemon", "checkpoint.shard", "fleet.replica_kill")

    def make_plan():
        return ComposedFaultPlan(seed=13, specs=[
            FaultSpec("store.daemon", "stall", at=2, count=1, arg=1.2),
            FaultSpec("checkpoint.shard", "bitflip", at=0, count=1, arg=4),
            FaultSpec("fleet.replica_kill", "kill", at=2, count=1,
                      match="replica:0:")])

    def shard_bytes(ckpt):
        with open(os.path.join(ckpt, "0_0.distcp"), "rb") as f:
            return f.read()

    prev = os.environ.get("PT_RETRY_DISABLE")
    if not recover:
        os.environ["PT_RETRY_DISABLE"] = "1"
    failures = []
    try:
        with tempfile.TemporaryDirectory() as tmp:
            plan = make_plan()
            store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1,
                             timeout=10.0, op_timeout=0.4)
            ckpt = os.path.join(tmp, "ckpt")

            def store_loop():
                try:
                    for i in range(6):
                        store.set(f"k{i}", str(i).encode())
                        if store.get(f"k{i}", wait=False) != str(i).encode():
                            failures.append(f"store: k{i} read back wrong")
                            return
                except Exception as e:
                    failures.append(f"store: {type(e).__name__}: {e}")

            def ckpt_loop():
                try:
                    save_state_dict({"w": w}, ckpt, replica=recover)
                    target = {"w": np.zeros_like(w)}
                    load_state_dict(target, ckpt)
                    if not np.array_equal(np.asarray(target["w"]), w):
                        failures.append("ckpt: replica returned wrong data")
                except Exception as e:
                    failures.append(f"ckpt: {type(e).__name__}: {e}")

            fleet = FleetRouter(_fleet_build, os.path.join(tmp, "fleet"),
                                num_replicas=3, failover=recover)
            reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
            threads = [threading.Thread(target=fn, daemon=True)
                       for fn in (store_loop, ckpt_loop)]
            try:
                with plan:
                    for t in threads:
                        t.start()
                    for r in reqs:
                        fleet.submit(r)
                    fleet.run_until_done(max_steps=500)
                    for t in threads:
                        t.join(timeout=60.0)
            finally:
                fleet.close()
                store.close()
            if any(t.is_alive() for t in threads):
                return False, "chaos thread(s) wedged past the join deadline"
            lost = [r.rid for r in reqs if r.failed or not r.done]
            if lost:
                failures.append(f"fleet: request(s) {lost} failed/unfinished")
            elif [list(r.tokens) for r in reqs] != refs:
                failures.append("fleet: streams diverged from the "
                                "uninterrupted reference")
            fired = plan.fired()
            damaged = shard_bytes(ckpt)
    finally:
        if prev is None:
            os.environ.pop("PT_RETRY_DISABLE", None)
        else:
            os.environ["PT_RETRY_DISABLE"] = prev
    if not recover:
        if not failures:
            return True, "unexpected: composed chaos bit nothing"
        return False, "recovery off: " + "; ".join(failures[:3])
    missing = [s for s in SITES if not fired.get(s)]
    if missing:
        return False, f"composed plan never fired site(s) {missing}"
    if failures:
        return False, "; ".join(failures[:3])
    # determinism across interleavings: a FRESH plan with the same seed
    # must damage the shard byte-identically even though run 1 had three
    # sites' threads racing (per-spec RNG streams, not one shared stream)
    with tempfile.TemporaryDirectory() as tmp2:
        replay = os.path.join(tmp2, "ckpt")
        with make_plan():
            save_state_dict({"w": w}, replay, replica=True)
        if shard_bytes(replay) != damaged:
            return False, ("per-spec RNG streams broke: the same seed "
                           "damaged the shard differently across runs")
    return True, (f"3 sites fired concurrently ({fired}), every recovery "
                  "path held, shard damage byte-identical across runs")


def drill_lifecycle_e2e(recover: bool):
    """The whole checkpoint lifecycle as ONE drill (docs/RESILIENCE.md
    "Checkpoint lifecycle"): train the tiny serving llama under a numeric
    guard with async checkpoints → an injected heartbeat loss kills the
    peer node and shrinks the mesh 8→4 devices → elastic resume over the
    survivors from the recorded checkpoint → train to completion →
    CheckpointPublisher digest-verifies the manifest and hot-swaps a live
    2-replica fleet via generation-fenced rolling restart → the swapped
    fleet serves byte-identically to a COLD engine built from the trained
    weights, and a second same-weights publish leaves every stream
    untouched. A ComposedFaultPlan arms three sites across the arc (store
    daemon stall, heartbeat kill, replica kill mid-wave). Control arm: no
    elastic manager, no failover — the same plan must flip the exit
    code."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.distributed.communication.store import TCPStore
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.resilience import (ComposedFaultPlan,
                                                   FaultSpec,
                                                   ResilientTrainer)
    from paddle_tpu.distributed.resilience.lifecycle import (
        CheckpointPublisher, lifecycle_stats, reset_lifecycle_stats,
        set_lifecycle_phase)
    from paddle_tpu.framework.numeric_guard import GuardPolicy
    from paddle_tpu.inference.fleet import FleetRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request)
    from paddle_tpu.models import LlamaForCausalLM

    cfg, _ = _serving_model()       # config only — models are drill-local
    B, S, STEPS = 8, 8, 6

    def _arr(v):
        return np.asarray(v._data if hasattr(v, "_data") else v)

    def data_fn(step):
        rng = np.random.default_rng(5000 + step)
        ids = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
        return ids, ids                 # self-supervised LM (shifted CE)

    def build(alive):
        n = 8 if len(alive) >= 2 else 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("dp",))
        paddle.seed(11)
        return Engine(LlamaForCausalLM(cfg), mesh, lr=1e-3, clip_norm=None,
                      guard=GuardPolicy(action="skip_step", warmup_steps=3,
                                        spike_factor=50.0))

    def serve_wave(fleet):
        reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
        for r in reqs:
            fleet.submit(r)
        fleet.run_until_done(max_steps=500)
        lost = [r.rid for r in reqs if r.failed or not r.done]
        return [list(r.tokens) for r in reqs], lost

    reset_lifecycle_stats()
    with tempfile.TemporaryDirectory() as tmp:
        store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1,
                         timeout=20.0)
        store_b = TCPStore("127.0.0.1", store.port, world_size=1,
                           timeout=20.0)
        plan = ComposedFaultPlan(seed=17, specs=[
            FaultSpec("store.daemon", "stall", at=4, count=1, arg=0.8),
            FaultSpec("elastic.heartbeat", "kill", at=3, count=-1,
                      match="nodeB"),
            FaultSpec("fleet.replica_kill", "kill", at=2, count=1,
                      match="replica:0:")])
        mgr_b = ElasticManager(store_b, "drill", "nodeB",
                               expected=["nodeA", "nodeB"],
                               heartbeat_interval=0.1, ttl=0.45)
        mgr_a = ElasticManager(store, "drill", "nodeA",
                               expected=["nodeA", "nodeB"],
                               heartbeat_interval=0.1, ttl=0.45) \
            if recover else None
        b_stop = threading.Event()

        def node_b_loop():
            i = 0
            while not b_stop.is_set():
                if mgr_b._thread is None or not mgr_b._thread.is_alive():
                    return              # heartbeat killed -> node is dead
                if i >= 3:
                    mgr_b.stop()        # deterministic death backstop
                    return
                try:
                    store_b.barrier(f"lcs{i}", world_size=2, timeout=3.0)
                except Exception:
                    return
                i += 1

        def coop_data_fn(step):
            ws = len(mgr_a.expected) if mgr_a is not None else 2
            if ws > 1:
                store.barrier(f"lcs{step}", world_size=ws, timeout=1.5)
            time.sleep(0.05)
            return data_fn(step)

        ckpt_dir = os.path.join(tmp, "job")
        plan.install()
        try:
            mgr_b.start()
            if mgr_a is not None:
                mgr_a.start()
            threading.Thread(target=node_b_loop, daemon=True).start()
            set_lifecycle_phase("train")
            trainer = ResilientTrainer(build, ckpt_dir, elastic=mgr_a,
                                       save_every=2)
            try:
                out = trainer.fit(coop_data_fn, STEPS)
            except Exception as e:
                return (False,
                        f"arc died in training: {type(e).__name__}: {e}")
            finally:
                b_stop.set()
                if mgr_a is not None:
                    mgr_a.stop()
                mgr_b.stop()

            if out["restarts"] < 1:
                return False, "peer loss never shrank the mesh"
            if not out["resumed_at"]:
                return False, "mesh shrank without an elastic resume"
            if out["final_step"] != STEPS:
                return False, f"train stopped at {out['final_step']}/{STEPS}"

            # publish: verify manifest -> load trained weights into the
            # live serving model -> generation-fenced rolling hot-swap
            paddle.seed(11)
            serve_model = LlamaForCausalLM(cfg)
            probe = sorted(serve_model.state_dict())[0]
            before = np.array(_arr(serve_model.state_dict()[probe]),
                              copy=True)

            def build_serve():
                return ContinuousBatchingEngine(serve_model, max_batch=2,
                                                max_len=32, page_size=8,
                                                block_size=2)

            publisher = CheckpointPublisher(ckpt_dir)
            fleet = FleetRouter(build_serve, os.path.join(tmp, "fleet"),
                                num_replicas=2, failover=recover)
            try:
                warm, lost0 = serve_wave(fleet)  # traffic on init weights
                if lost0:
                    return False, f"pre-publish wave lost request(s) {lost0}"
                pub = publisher.publish(serve_model, fleet)
                swapped, lost1 = serve_wave(fleet)
                pub2 = publisher.publish(serve_model, fleet)  # same weights
                again, lost2 = serve_wave(fleet)
            finally:
                fleet.close()

            if lost1 or lost2:
                return False, (f"post-publish wave lost request(s) "
                               f"{lost1 or lost2}")
            if pub["generation"] < 1 or pub["shards"] < 1 or pub["params"] < 1:
                return False, f"publish record looks torn: {pub}"
            if pub2["generation"] != pub["generation"]:
                return False, "same-weights republish changed generation"
            if np.array_equal(before,
                              _arr(serve_model.state_dict()[probe])):
                return False, "publish did not change the serving weights"

            # byte-identity contract: the hot-swapped fleet == a COLD
            # engine built from the published checkpoint; a same-weights
            # swap changes nothing
            cold_model = LlamaForCausalLM(cfg)
            publisher.load_weights(cold_model, pub["step"])
            cold = ContinuousBatchingEngine(cold_model, max_batch=2,
                                            max_len=32, page_size=8,
                                            block_size=2)
            cold_reqs = [Request(**kw) for kw in _fleet_wave_kwargs()]
            for r in cold_reqs:
                cold.add_request(r)
            cold.run_until_done(max_steps=500)
            cold_refs = [list(r.tokens) for r in cold_reqs]
        finally:
            plan.uninstall()
            store_b.close()
            store.close()

    if swapped != cold_refs:
        bad = [i for i, (s, c) in enumerate(zip(swapped, cold_refs))
               if s != c]
        return False, (f"hot-swapped stream(s) {bad} diverged from a cold "
                       "engine on the published weights")
    if again != swapped:
        return False, ("same-weights swap changed served streams "
                       "(before/after byte-identity broken)")
    fired = plan.fired()
    missing = [s for s in ("store.daemon", "elastic.heartbeat",
                           "fleet.replica_kill") if not fired.get(s)]
    if missing:
        return False, f"composed plan never fired site(s) {missing}"
    stats = lifecycle_stats()
    if (stats["publish_total"] != 2
            or stats["generation"] != pub["generation"]
            or stats["phase"] != "serve"):
        return False, f"lifecycle stats out of step: {stats}"
    return True, (f"8->4 shrink resumed at step {out['resumed_at'][0]}, "
                  f"published gen {pub['generation']} ({pub['shards']} "
                  f"shard(s), {pub['params']} params), hot-swap == cold "
                  f"engine, same-weights swap byte-stable, 3 chaos sites "
                  f"fired {fired}")


DRILLS = {
    "heartbeat": drill_heartbeat,
    "store_stall": drill_store_stall,
    "shard_corruption": drill_shard_corruption,
    "engine_saturation": drill_engine_saturation,
    "serving_deadline": drill_serving_deadline,
    "prefix_cache_exhaustion": drill_prefix_cache_exhaustion,
    "big_batch_saturation": drill_big_batch_saturation,
    "serving_crash": drill_serving_crash,
    "mesh_device_loss": drill_mesh_device_loss,
    "serving_stall": drill_serving_stall,
    "serving_overload_shed": drill_serving_overload_shed,
    "fleet_replica_kill": drill_fleet_replica_kill,
    "fleet_proc_kill": drill_fleet_proc_kill,
    "net_flaky_migration": drill_net_flaky_migration,
    "net_slow_peer": drill_net_slow_peer,
    "fleet_drain": drill_fleet_drain,
    "fleet_overload": drill_fleet_overload,
    "kv_migration_corruption": drill_kv_migration_corruption,
    "spec_decode_divergence": drill_spec_decode_divergence,
    "nan_grad": drill_nan_grad,
    "loss_spike": drill_loss_spike,
    "poison_batch": drill_poison_batch,
    "composed_chaos": drill_composed_chaos,
    "lifecycle_e2e": drill_lifecycle_e2e,
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--drill", choices=sorted(DRILLS))
    ap.add_argument("--no-recover", action="store_true",
                    help="disable the drill's recovery path (must flip rc)")
    ap.add_argument("--selftest", action="store_true",
                    help="run the full matrix, both recovery modes")
    ap.add_argument("--only", default=None, metavar="A,B,...",
                    help="selftest subset: run only these drills")
    ap.add_argument("--skip", default=None, metavar="A,B,...",
                    help="selftest subset: run all but these drills "
                         "(local iteration on one drill family)")
    args = ap.parse_args(argv)

    if args.selftest:
        selected = dict(DRILLS)
        for flag, keep in ((args.only, True), (args.skip, False)):
            if flag is None:
                continue
            names = [n.strip() for n in flag.split(",") if n.strip()]
            unknown = [n for n in names if n not in DRILLS]
            if unknown:
                ap.error(f"unknown drill(s): {', '.join(unknown)}")
            selected = {k: v for k, v in selected.items()
                        if (k in names) == keep}
        h = _selftest.Harness("FAULT DRILL")
        for name, drill in selected.items():
            ok, info = drill(recover=True)
            h.case(f"{name} (recovery on)", ok, info)
            ok2, info2 = drill(recover=False)
            h.case(f"{name} (recovery off, fault must bite)", not ok2, info2)
        from paddle_tpu.distributed.resilience import retry_stats

        rs = retry_stats()
        h.note(f"retry stats: {rs['calls']} calls, {rs['attempts']} "
               f"attempts, {rs['retries']} retries, {rs['giveups']} "
               f"give-ups, {rs['latency_s']:.2f}s cumulative latency")
        return h.finish(
            f"FAULT DRILL OK: {len(selected)} fault classes recovered, "
            "each flips the gate without its recovery path",
            "FAULT DRILL FAIL: {failures} expectation(s) violated")

    if not args.drill:
        print(__doc__)
        return 2
    ok, info = DRILLS[args.drill](recover=not args.no_recover)
    print(f"[{'ok' if ok else 'FAIL'}] {args.drill}: {info}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
