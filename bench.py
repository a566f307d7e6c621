"""Benchmark suite: one JSON line per config, north-star config LAST.

Configs (BASELINE.md matrix):
  1. resnet18_cifar_images_per_sec      — conv path through XLA (config #1)
  2. bert_base_ft_tokens_per_sec        — encoder bf16 fine-tune step (config #2)
  3. llama_750M_seq2048 (legacy line)   — round-1 comparison point
  4. llama_1B_seq4096_gqa_remat (LAST)  — the north-star-faithful config:
     seq 4096, GQA 4:1, remat ON, largest llama fitting one chip with fp32
     AdamW state. vs_baseline = achieved MFU / 0.40 (BASELINE.json target).

Every config trains on FRESH random batches each step (no single-batch
memorization); the reported loss is the running train loss on that stream.

Model-FLOPs use the PaLM appendix formula: 6*N per token + 12*L*H*Q*T
attention (causal halves it).
"""

from __future__ import annotations

import contextlib
import json
import os as _os
import time

import numpy as np

# the mesh-sharded serving arm needs >1 host (cpu) device; the flag only
# affects the CPU backend (TPU device counts are untouched) and must land
# before jax initializes — same bootstrap as tests/conftest.py
_flags = _os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# bf16 peak FLOP/s per chip by TPU generation (order matters: most specific first)
PEAK_FLOPS = (
    ("v6e", 918e12),
    ("v6", 918e12),
    ("v5 lite", 197e12),
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
)


def _device_peak(dev) -> float:
    kind = getattr(dev, "device_kind", "").lower()
    for key, val in PEAK_FLOPS:
        if key in kind:
            return val
    raise ValueError(
        f"no bf16 peak recorded for device_kind {dev.device_kind!r} "
        f"(platform {dev.platform}); add it to PEAK_FLOPS")


# phases that raised; main() exits non-zero if any did
_FAILED = []


def _phase_failed(name, exc):
    _FAILED.append(name)
    print(f"# {name} bench failed: {exc!r}", flush=True)


def _emit(metric, value, unit, vs_baseline):
    # vs_baseline=None → JSON null: BASELINE.json defines no denominator for
    # this line (only the north-star MFU target exists); never fabricate 1.0.
    print(json.dumps({
        "metric": metric,
        "value": round(float(value), 2),
        "unit": unit,
        "vs_baseline": (None if vs_baseline is None
                        else round(float(vs_baseline), 4)),
    }), flush=True)


def bench_llama(name, cfg, batch, seq, iters, dev):
    """Fused train-step throughput (fwd + bwd + clip + AdamW) on one chip."""
    import jax

    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import LlamaForCausalLM

    model = LlamaForCausalLM(cfg)
    eng = Engine(model, mesh=None, lr=1e-4, clip_norm=1.0)

    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
               for _ in range(iters)]

    # warmup (compile); fetching the loss to the host fences the step
    loss = eng.step(batches[0], batches[0])
    jax.device_get(loss)
    loss = eng.step(batches[0], batches[0])
    jax.device_get(loss)

    t0 = time.perf_counter()
    for ids in batches:
        loss = eng.step(ids, ids)  # fresh batch each step — no memorization
    # params of step i feed step i+1, so fetching the last loss fences the chain
    jax.device_get(loss)
    dt = time.perf_counter() - t0

    tok_per_sec = batch * seq * iters / dt
    n_params = cfg.num_params()
    L, H, Q = cfg.num_hidden_layers, cfg.num_attention_heads, cfg.head_dim
    # fwd+bwd model flops per token: 6N + causal attention 12*L*(H*Q)*seq/2
    flops_per_token = 6.0 * n_params + 6.0 * L * (H * Q) * seq
    mfu = tok_per_sec * flops_per_token / _device_peak(dev)
    _emit(name, tok_per_sec,
          f"tokens/s ({n_params/1e6:.0f}M params bf16 seq{seq} "
          f"kv{cfg.num_key_value_heads}/{H} remat={cfg.recompute}, "
          f"loss {float(loss):.3f}, mfu {mfu:.3f})",
          mfu / 0.40)
    return mfu


def bench_resnet(dev, on_tpu):
    """ResNet-18 CIFAR-class training throughput (BASELINE.md config #1)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet18

    model = resnet18(num_classes=10)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    batch = 256 if on_tpu else 16
    iters = 8 if on_tpu else 2
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(batch, 3, 32, 32)).astype(np.float32)
          for _ in range(iters)]
    ys = [rng.integers(0, 10, (batch,)).astype(np.int64) for _ in range(iters)]

    from paddle_tpu.hapi.model import Model

    m = Model(model)
    m.prepare(optimizer=opt, loss=paddle.nn.CrossEntropyLoss())

    loss, _ = m.train_batch(paddle.to_tensor(xs[0]), paddle.to_tensor(ys[0]))
    t0 = time.perf_counter()
    for x, y in zip(xs, ys):
        loss, _ = m.train_batch(paddle.to_tensor(x), paddle.to_tensor(y))
    dt = time.perf_counter() - t0  # train_batch host-syncs the loss per step
    ips = batch * iters / dt
    _emit("resnet18_cifar_images_per_sec", ips,
          f"images/s (batch {batch}, fp32, loss {loss[0]:.3f})", None)


def _scalar(x):
    import jax

    arr = np.asarray(jax.device_get(x._data if hasattr(x, "_data") else x))
    return float(arr.reshape(-1)[0])


def bench_bert(dev, on_tpu):
    """BERT-base bf16 fine-tune step throughput (BASELINE.md config #2)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models.bert.modeling import BertConfig, BertForSequenceClassification

    cfg = (BertConfig(dtype="bfloat16", hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0) if on_tpu
           else BertConfig.tiny())
    model = BertForSequenceClassification(cfg)
    eng = Engine(model, mesh=None, lr=2e-5, clip_norm=1.0,
                 loss_fn=lambda ids, lbl: model.loss_fn(ids, lbl))
    batch, seq = (32, 128) if on_tpu else (4, 32)
    iters = 8 if on_tpu else 2
    rng = np.random.default_rng(0)
    idss = [rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
            for _ in range(iters)]
    lbls = [rng.integers(0, 2, (batch,)).astype(np.int32) for _ in range(iters)]

    loss = eng.step(idss[0], lbls[0])
    jax.device_get(loss)
    t0 = time.perf_counter()
    for ids, lbl in zip(idss, lbls):
        loss = eng.step(ids, lbl)
    jax.device_get(loss)
    dt = time.perf_counter() - t0
    tps = batch * seq * iters / dt
    _emit("bert_base_ft_tokens_per_sec", tps,
          f"tokens/s (bf16 seq {seq} batch {batch}, loss {_scalar(loss):.3f})",
          None)


def bench_serving(dev, on_tpu):
    """Continuous-batching serving throughput vs dense-cache generate().

    Config per the serving suite's design point: llama-750M-class bf16,
    8 slots, greedy, HETEROGENEOUS request lengths (max_new cycling
    16/32/48/64), REPEATED-SYSTEM-PROMPT prompts (48 of 64 tokens shared —
    the workload prefix caching exists for) served through the radix
    prefix cache + chunked prefill (docs/SERVING.md). Both sides count
    USEFUL tokens (what each request asked for) and fully materialize
    outputs (generate() dispatches asynchronously — unsynced timings are
    dispatch-time fiction). vs_baseline = engine / dense useful-tokens/s.
    A cache-DISABLED engine runs the same wave as the cold-cache guard
    (legacy programs, printed as a comment) and hosts the p99 section.
    """
    import time as _t

    import jax

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16")
        n_req, prompt_len, shared_len, max_new, slots, block, page = (
            16, 64, 48, 64, 8, 16, 16)
    else:
        cfg = LlamaConfig.tiny()
        n_req, prompt_len, shared_len, max_new, slots, block, page = (
            4, 16, 8, 8, 2, 4, 8)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, (shared_len,)).astype(np.int32)
    prompts = [np.concatenate([
        system,
        rng.integers(0, cfg.vocab_size,
                     (prompt_len - shared_len,)).astype(np.int32)])
        for _ in range(n_req)]
    # heterogeneous request sizes: 1/4, 2/4, 3/4, 4/4 of max_new
    new_toks = [(i % 4 + 1) * max_new // 4 for i in range(n_req)]
    useful = sum(new_toks)

    # dense-cache generate() baseline: full batches, every row decoded to
    # the batch max (the dense API has one max_new per call)
    ids = np.stack(prompts[:slots])
    np.asarray(model.generate(ids, max_new_tokens=max_new,
                              temperature=0.0).numpy())  # compile

    def dense_wave():
        for lo in range(0, n_req, slots):
            out = model.generate(np.stack(prompts[lo:lo + slots]),
                                 max_new_tokens=max_new, temperature=0.0)
            np.asarray(out.numpy())

    # ONE engine per mode for warmup + timing: jit caches key on the
    # engine's closures, so a fresh engine would re-trace/compile inside
    # the timed window. `eng` = legacy programs (prefix cache off): the
    # cold-cache guard and the p99 host. `peng` = prefix cache + chunked
    # prefill; its warmup wave also PRIMES the radix cache, so timed waves
    # measure the steady repeated-system-prompt state.
    eng = ContinuousBatchingEngine(
        model, max_batch=slots, max_len=prompt_len + max_new,
        page_size=page, block_size=block, prompt_buckets=[prompt_len])
    peng = ContinuousBatchingEngine(
        model, max_batch=slots, max_len=prompt_len + max_new,
        page_size=page, block_size=block,
        prefix_cache=PrefixCacheConfig(extra_blocks=slots))

    def run_wave(e):
        e.stats["admit_host_s"] = e.stats["decode_host_s"] = 0.0
        for p, k in zip(prompts, new_toks):
            e.add_request(Request(p, max_new_tokens=k))
        e.run_until_done()

    run_wave(eng)                                  # compile legacy programs
    run_wave(peng)                                 # compile + prime cache

    def timed(fn, *a):
        t0 = _t.perf_counter()
        fn(*a)
        return _t.perf_counter() - t0

    # best-of-3, INTERLEAVED dense/cold/warm so monotone chip-state drift
    # hits every side equally (single-shot decode timings swung 2x+ in the
    # July 2026 records — ratios of 1.1x-2.0x for identical code; the
    # spread is not re-measured on the direct runtime)
    hits0 = peng.stats["hit_tokens"]
    total0 = hits0 + peng.stats["miss_tokens"]
    dt_dense = dt_cold = dt = float("inf")
    for _ in range(3):
        dt_dense = min(dt_dense, timed(dense_wave))
        dt_cold = min(dt_cold, timed(run_wave, eng))
        dt = min(dt, timed(run_wave, peng))
    hit_rate = ((peng.stats["hit_tokens"] - hits0)
                / max(1, peng.stats["hit_tokens"]
                      + peng.stats["miss_tokens"] - total0))
    share = peng.stats["admit_host_s"] / max(dt, 1e-9)
    print(f"# serving admit-host share (last wave admit time / best wave "
          f"time): {share:.3f}", flush=True)
    print(f"# serving cold-cache (prefix cache off, legacy programs): "
          f"{useful / dt_cold:.0f} useful tok/s — same code path as the "
          f"pre-prefix-cache engine, so cold throughput is regression-free "
          f"by construction", flush=True)
    print(f"# serving prefix-cache block lifecycle: "
          f"cow_copies={peng.stats['cow_copies']} "
          f"evictions={peng.stats['evictions']} "
          f"compiled={peng.stats['compile_cache_entries']}", flush=True)
    dense_tps = useful / dt_dense
    eng_tps = useful / dt
    _emit("serving_tokens_per_sec", eng_tps,
          f"useful tok/s (llama-750M bf16 prefix-cache, {slots} slots, "
          f"prompt {prompt_len} shared {shared_len}, max_new "
          f"{max_new // 4}-{max_new} mixed, block {block}; "
          f"dense generate batch-{slots} "
          f"decode-to-max: {dense_tps:.0f} useful tok/s)",
          eng_tps / dense_tps)
    _emit("serving_prefix_hit_rate", hit_rate,
          f"fraction of prompt tokens served from the radix prefix cache "
          f"(timed waves, {n_req} reqs, shared {shared_len}/{prompt_len})",
          None)

    # prefill-bound wave: max_new=1 isolates admission+prefill; tokens/s
    # counts ALL prompt tokens (cache hits included — that is the point)
    def prefill_wave():
        for p in prompts:
            peng.add_request(Request(p, max_new_tokens=1))
        peng.run_until_done()

    prefill_wave()                                 # compile the g-variants
    dt_pre = min(timed(prefill_wave), timed(prefill_wave))
    _emit("serving_prefill_tokens_per_sec", n_req * prompt_len / dt_pre,
          f"prompt tok/s (max_new=1 wave, warm radix cache, {slots} slots, "
          f"prompt {prompt_len} shared {shared_len})", None)

    # p99 per-step latency WITH request deadlines enabled (deadlines far
    # beyond the wave length, so the scan runs but never evicts): pins the
    # resilience hooks — deadline/eviction bookkeeping, queue accounting —
    # as overhead-neutral on the serving hot path. Compared against the
    # recorded baseline by tools/check_bench_regression.py (SECONDARY).
    for p, k in zip(prompts, new_toks):
        eng.add_request(Request(p, max_new_tokens=k, deadline_s=3600.0))
    step_s = []
    while eng.has_work():
        t0 = _t.perf_counter()
        eng.step()
        step_s.append(_t.perf_counter() - t0)
    eng.finished()
    p99 = float(np.quantile(np.asarray(step_s), 0.99)) * 1e3
    _emit("serving_p99_step_latency_ms", p99,
          f"ms (p99 engine step, deadlines enabled, {len(step_s)} steps, "
          f"{slots} slots)", None)


def bench_serving_large_batch(dev, on_tpu):
    """Big-batch fused mega-step serving (ISSUE 10 / ROADMAP item 3):
    128 slots, device-resident tables, packed prefill, O(active) host
    bookkeeping — docs/SERVING.md.

    - ``serving_large_batch_tokens_per_sec``: useful tok/s over a mixed
      prompt/max_new wave at 128 slots (2x oversubscribed, shared system
      prompt through the radix cache). SECONDARY ("higher").
    - ``serving_step_host_share_pct``: host-side time (admit + decode
      dispatch + prefill bookkeeping) as a share of wave wall time at 128
      slots. The acceptance claim is SUBLINEAR growth of host us/step in
      slot count (counter-based bookkeeping, no O(max_batch) per-step
      scans) — an 8-slot fused engine runs the same wave shape and the
      per-step ratio prints as a comment. SECONDARY ("lower", 5%% floor —
      CPU tiny reads are noisy like guard_overhead_pct).
    - ``observability_overhead_big_batch_pct``: the same 128-slot warm
      wave fully instrumented (TraceRecorder attached, batched per-step
      stamps — one lock acquisition per decode block, not per slot) vs
      bare, best-of-3 interleaved. SECONDARY ("lower", 5%% floor).
    """
    import time as _t

    import jax

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import TraceRecorder

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16")
        slots, prompt_len, shared_len, max_new, block, page = (
            128, 64, 48, 64, 16, 16)
    else:
        cfg = LlamaConfig.tiny(num_hidden_layers=1)
        slots, prompt_len, shared_len, max_new, block, page = (
            128, 16, 8, 8, 4, 8)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    n_req = slots * 2
    system = rng.integers(0, cfg.vocab_size, (shared_len,)).astype(np.int32)
    prompts = [np.concatenate([
        system,
        rng.integers(0, cfg.vocab_size,
                     (prompt_len - shared_len,)).astype(np.int32)])
        for _ in range(n_req)]
    new_toks = [(i % 4 + 1) * max_new // 4 for i in range(n_req)]
    useful = sum(new_toks)

    def build(n_slots, tracer=None):
        return ContinuousBatchingEngine(
            model, max_batch=n_slots, max_len=prompt_len + max_new,
            page_size=page, block_size=block,
            prefix_cache=PrefixCacheConfig(extra_blocks=n_slots),
            tracer=tracer)

    def run_wave(e, ps=None, ks=None):
        for k in ("admit_host_s", "decode_host_s", "prefill_host_s"):
            e.stats[k] = 0.0
        s0 = e._step_idx
        for p, k in zip(ps or prompts, ks or new_toks):
            e.add_request(Request(p, max_new_tokens=k))
        e.run_until_done(max_steps=20000)
        return e._step_idx - s0

    def timed(fn, *a):
        t0 = _t.perf_counter()
        fn(*a)
        return _t.perf_counter() - t0

    def host_s(e):
        # admit_host_s already contains the prefill tick (its timer nests
        # inside the admit window) — don't double-count prefill_host_s
        return e.stats["admit_host_s"] + e.stats["decode_host_s"]

    eng = build(slots)
    run_wave(eng)                                  # compile + prime radix
    dt, host, steps = float("inf"), 0.0, 1
    for _ in range(3):                             # best-of-3, host+wall
        t0 = _t.perf_counter()                     # from the SAME wave
        n_steps = run_wave(eng) or 1
        dt_w = _t.perf_counter() - t0
        if dt_w < dt:
            dt, host, steps = dt_w, host_s(eng), n_steps
    share = 100.0 * host / max(dt, 1e-9)

    # sublinearity reference: the SAME fused code path at 8 slots serving
    # the same per-slot load (n_req scaled down with the slot count)
    small = build(8)
    sp, sk = prompts[:16], new_toks[:16]
    run_wave(small, sp, sk)
    run_wave(small, sp, sk)
    small_steps = run_wave(small, sp, sk) or 1
    small_host_us = 1e6 * host_s(small) / small_steps
    big_host_us = 1e6 * host / steps
    print(f"# serving big-batch host us/step: {big_host_us:.0f} at {slots} "
          f"slots vs {small_host_us:.0f} at 8 slots -> "
          f"{big_host_us / max(small_host_us, 1e-9):.1f}x for 16x slots "
          f"(sublinear = counter-based bookkeeping holding)", flush=True)
    print(f"# serving big-batch stats: packed_rows="
          f"{eng.stats['packed_rows']} fused_updates="
          f"{eng.stats['fused_updates']} cow={eng.stats['cow_copies']} "
          f"compiled={eng.stats['compile_cache_entries']}", flush=True)
    _emit("serving_large_batch_tokens_per_sec", useful / dt,
          f"useful tok/s (fused mega-step, {slots} slots, {n_req} reqs, "
          f"prompt {prompt_len} shared {shared_len}, max_new "
          f"{max_new // 4}-{max_new} mixed, block {block})", None)
    _emit("serving_step_host_share_pct", share,
          f"% of wave wall spent host-side ({steps} steps, "
          f"{big_host_us:.0f} us/step at {slots} slots vs "
          f"{small_host_us:.0f} at 8)", None)

    # observability at big batch: the PR 9 stamp RLock must not serialize
    # a 128-row step — batched stamps keep this near the bare wave
    tracer = TraceRecorder()
    ieng = build(slots, tracer=tracer)
    run_wave(ieng)                                 # compile + prime
    dt_i = dt_b = float("inf")
    for _ in range(3):
        dt_i = min(dt_i, timed(run_wave, ieng))
        dt_b = min(dt_b, timed(run_wave, eng))
    pct = 100.0 * (dt_i - dt_b) / max(dt_b, 1e-9)
    _emit("observability_overhead_big_batch_pct", max(0.0, pct),
          f"% wave slowdown fully instrumented vs bare at {slots} slots "
          f"(batched per-step stamps; best-of-3 interleaved)", None)


def bench_serving_recovery(dev, on_tpu):
    """Serving resilience envelope (docs/SERVING.md): crash-recovery wall
    time and overload shed rate.

    - ``serving_recovery_time_s``: a FaultPlan ``serving.step`` kill lands
      mid-decode; the ServingSupervisor rebuilds the engine from the
      request journal and replays to the delivered high-water marks. The
      metric is the supervisor's measured rebuild+replay time — dominated
      by program recompiles on the fresh engine, which is exactly the cost
      a production operator eats per crash. SECONDARY-guarded ("lower",
      2s floor) by tools/check_bench_regression.py.
    - ``serving_shed_rate``: a wave with deliberately infeasible deadlines
      mixed in; the rate is shed/submitted. If feasibility shedding breaks,
      the rate collapses toward 0 (infeasible requests queue and die by
      deadline eviction instead) — guarded in the "higher" direction.
    """
    import os
    import tempfile

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request, RequestShed,
                                              ServingSupervisor)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=512,
            dtype="bfloat16")
        slots, max_len, page, block, n_req, max_new = 4, 256, 16, 8, 8, 48
    else:
        cfg = LlamaConfig.tiny()
        slots, max_len, page, block, n_req, max_new = 2, 32, 8, 2, 4, 8
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (page,)).astype(np.int32)
               for _ in range(n_req)]

    def build():
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block, prefix_cache=True)

    def wave(sup):
        reqs = [Request(p, max_new_tokens=max_new, seed=10 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            sup.submit(r)
        sup.run_until_done(max_steps=5000)
        return reqs

    with tempfile.TemporaryDirectory() as tmp:
        sup = ServingSupervisor(build, os.path.join(tmp, "bench.jrnl"))
        wave(sup)                               # warm + journal baseline
        plan = FaultPlan(seed=7, specs=[
            FaultSpec("serving.step", "kill", at=2, count=1)])
        with plan:
            reqs = wave(sup)
        sup.close()
        ok = all(r.done and not r.failed for r in reqs)
        if sup.recoveries < 1 or not ok:
            print(f"# serving recovery bench: no crash absorbed "
                  f"(recoveries={sup.recoveries}, ok={ok})", flush=True)
        else:
            _emit("serving_recovery_time_s", sup.stats["recovery_s"],
                  f"s (rebuild + replay-to-hwm after a mid-decode engine "
                  f"kill; {sup.stats['replayed_requests']} request(s) "
                  f"replayed, {slots} slots, prefix cache on)", None)

    # shed rate: warm engine -> feasible load + infeasible-deadline burst
    eng = ContinuousBatchingEngine(model, max_batch=slots, max_len=max_len,
                                   page_size=page, block_size=block)
    warm = Request(prompts[0], max_new_tokens=max_new)
    eng.add_request(warm)
    eng.run_until_done(max_steps=2000)          # compiles + measures rate
    submitted = shed = 0
    live = []
    for i, p in enumerate(prompts):
        feasible = Request(p, max_new_tokens=max_new, seed=30 + i)
        submitted += 1
        try:
            eng.add_request(feasible)
            live.append(feasible)
        except RequestShed:
            shed += 1
        doomed = Request(p, max_new_tokens=max_new, deadline_s=1e-3,
                         seed=60 + i)
        submitted += 1
        try:
            eng.add_request(doomed)
            live.append(doomed)
        except RequestShed:
            shed += 1
    eng.run_until_done(max_steps=5000)
    _emit("serving_shed_rate", shed / max(1, submitted),
          f"fraction of submissions shed at submit (PT-SRV-003; "
          f"{submitted} submitted, half with infeasible 1ms deadlines, "
          f"{sum(r.done and not r.failed for r in live)} served)", None)


def bench_serving_mesh_degrade(dev, on_tpu):
    """Elastic mesh-degrade wall time (docs/RESILIENCE.md "Elastic serving
    mesh").

    ``serving_mesh_degrade_time_s``: a ``device.loss`` fault removes 2 of
    a tp=4 engine's devices mid-decode; the elastic ServingSupervisor
    harvests the column shards host-side, rebuilds at tp=2, re-splits the
    same bytes, and replays to the delivered high-water marks — streams
    byte-identical by contract. The metric is the supervisor's measured
    reshard+replay time, dominated by the tp=2 program recompiles on the
    rebuilt engine (exactly the cost an operator eats per device-group
    loss). SECONDARY-guarded ("lower", 2s floor) by
    tools/check_bench_regression.py."""
    import os
    import tempfile

    import jax

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.recovery import ServingSupervisor
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              MeshConfig, PrefixCacheConfig,
                                              Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if len(jax.devices()) < 4:
        print("# serving mesh degrade bench skipped: <4 devices", flush=True)
        return
    # 4 kv heads so tp=4 is buildable AND tp=2 survives the shrink
    cfg = LlamaConfig.tiny(num_key_value_heads=4)
    slots, max_len, page, block, n_req, max_new = 2, 32, 8, 2, 4, 8
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (page,)).astype(np.int32)
               for _ in range(n_req)]

    def build(mesh_tp=4):
        mesh = None if mesh_tp is None else MeshConfig(tp=int(mesh_tp))
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block,
            prefix_cache=PrefixCacheConfig(extra_blocks=slots), mesh=mesh)

    def wave(sup):
        reqs = [Request(p, max_new_tokens=max_new, seed=10 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            sup.submit(r)
        sup.run_until_done(max_steps=5000)
        return reqs

    with tempfile.TemporaryDirectory() as tmp:
        sup = ServingSupervisor(build, os.path.join(tmp, "bench.jrnl"))
        wave(sup)                           # warm the tp=4 programs
        base_s = sup.stats["recovery_s"]
        plan = FaultPlan(seed=9, specs=[
            FaultSpec("device.loss", "lose", at=2, count=1, arg=2)])
        with plan:
            reqs = wave(sup)
        tp = (int(sup.engine.mesh.tp)
              if getattr(sup.engine, "mesh", None) is not None else 1)
        ok = all(r.done and not r.failed for r in reqs)
        sup.close()
        if sup.stats["mesh_reshards"] < 1 or tp != 2 or not ok:
            print(f"# serving mesh degrade bench: degrade not absorbed "
                  f"(reshards={sup.stats['mesh_reshards']}, tp={tp}, "
                  f"ok={ok})", flush=True)
        else:
            _emit("serving_mesh_degrade_time_s",
                  sup.stats["recovery_s"] - base_s,
                  f"s (harvest + rebuild tp=4->2 + replay-to-hwm after "
                  f"losing 2 devices mid-decode; "
                  f"{sup.stats['replayed_requests']} request(s) replayed, "
                  f"recompile-dominated)", None)


def bench_checkpoint_publish(dev, on_tpu):
    """Checkpoint publish wall time (docs/RESILIENCE.md "Checkpoint
    lifecycle"): digest-verify the manifest, map the checkpoint's params
    into the live serving model in place, and hot-swap a warm 2-replica
    fleet via rolling restart. Dominated by the rebuilt replicas' program
    recompiles — exactly the cost an operator eats per weight push.
    SECONDARY-guarded ("lower", 2s floor) by
    tools/check_bench_regression.py."""
    import os
    import tempfile

    from paddle_tpu.distributed.checkpoint import save_state_dict
    from paddle_tpu.distributed.checkpoint.latest import commit_latest
    from paddle_tpu.distributed.resilience.lifecycle import \
        CheckpointPublisher
    from paddle_tpu.inference.fleet import FleetRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=512,
            dtype="bfloat16")
        slots, max_len, page, block, n_req, max_new = 4, 256, 16, 8, 8, 48
    else:
        cfg = LlamaConfig.tiny()
        slots, max_len, page, block, n_req, max_new = 2, 32, 8, 2, 4, 8
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (page,)).astype(np.int32)
               for _ in range(n_req)]

    def build():
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block)

    with tempfile.TemporaryDirectory() as tmp:
        ckpt = os.path.join(tmp, "ckpt")
        step = 100
        save_state_dict({"model": model.state_dict()},
                        os.path.join(ckpt, f"step_{step:08d}"))
        commit_latest(ckpt, step, 1)
        fleet = FleetRouter(build, os.path.join(tmp, "fleet"),
                            num_replicas=2)
        reqs = [Request(p, max_new_tokens=max_new, seed=10 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:                          # warm every replica first:
            fleet.submit(r)                     # the swap cost measured is
        fleet.run_until_done(max_steps=5000)    # rebuild, not cold compile
        pub = CheckpointPublisher(ckpt).publish(model, fleet)
        fleet.close()
    _emit("checkpoint_publish_time_s", pub["time_s"],
          f"s (digest-verify {pub['shards']} shard(s) + in-place load of "
          f"{pub['params']} params + rolling hot-swap of 2 warm replicas, "
          f"gen {pub['generation']}; recompile-dominated)", None)


def bench_fleet(dev, on_tpu):
    """Fleet serving envelope (docs/SERVING.md fleet section): 3-replica
    FleetRouter aggregate throughput and journal-backed failover time.

    - ``fleet_tokens_per_sec``: useful tok/s of a 3-replica fleet over a
      mixed wave; vs_baseline = fleet / ONE supervisor-wrapped replica on
      the identical wave. All replicas share this process's single device,
      so the ratio reads as fleet-LAYER overhead (routing, per-replica
      journals, twin splicing) rather than scale-out — the >=2x scaling
      claim needs one device per replica; the SECONDARY guard protects the
      recorded single-device ratio from regressing.
    - ``fleet_failover_time_s``: a ``fleet.replica_kill`` fault lands
      mid-wave; the metric is the router's measured journal-load +
      re-admit + catch-up-to-high-water-mark time (dominated by program
      recompiles on the surviving replicas' fresh admissions — the cost an
      operator eats per replica loss). SECONDARY ("lower", 2s floor).
    - ``fleet_proc_tokens_per_sec``: the PROCESS-per-replica arm
      (inference/procfleet): 2 spawned worker processes, each with its own
      jax runtime/model/journal, stepped with ``parallel_step`` so replica
      programs overlap; vs_baseline = 2-process fleet / ONE worker process
      on the identical wave — the first scale-OUT ratio in the series (the
      in-process fleet shares one device, so its ratio reads as router
      overhead). Workers are pinned to host (CPU) devices: on a TPU host
      two processes cannot share the chip, and on CPU the ratio is capped
      by host-core weather — ≥1.5x expected on an idle ≥4-core box, lower
      under CI contention. SECONDARY ("higher").
    """
    import os
    import tempfile

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.fleet import FleetConfig, FleetRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request, ServingSupervisor)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    import time as _t

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=512,
            dtype="bfloat16")
        slots, max_len, page, block, n_req, max_new, plen = (
            4, 256, 16, 8, 18, 48, 16)
    else:
        cfg = LlamaConfig.tiny()
        slots, max_len, page, block, n_req, max_new, plen = (
            2, 32, 8, 4, 12, 16, 16)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
               for _ in range(n_req)]

    def build():
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block, prompt_buckets=[plen])

    def wave(target):
        reqs = [Request(p, max_new_tokens=max_new, seed=500 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            target.submit(r)
        target.run_until_done(max_steps=20000)
        return reqs

    def timed(target):
        t0 = _t.perf_counter()
        wave(target)
        return _t.perf_counter() - t0

    useful = n_req * max_new
    with tempfile.TemporaryDirectory() as tmp:
        single = ServingSupervisor(build, os.path.join(tmp, "single.jrnl"))
        fleet = FleetRouter(build, os.path.join(tmp, "fleet"),
                            num_replicas=3,
                            config=FleetConfig(brownout_depth=10 ** 9))
        wave(single)                        # compile the single replica
        wave(fleet)                         # compile all three replicas
        dt_single = dt_fleet = float("inf")
        for _ in range(3):                  # interleaved best-of-3
            dt_single = min(dt_single, timed(single))
            dt_fleet = min(dt_fleet, timed(fleet))
        single_tps = useful / dt_single
        fleet_tps = useful / dt_fleet
        _emit("fleet_tokens_per_sec", fleet_tps,
              f"useful tok/s (3-replica FleetRouter, {slots} slots/replica, "
              f"{n_req} reqs max_new {max_new}, per-replica journals; "
              f"single supervisor-wrapped replica on the same wave + "
              f"device: {single_tps:.0f} tok/s)",
              fleet_tps / single_tps)

        # failover: kill replica 0 mid-wave, measure journal-backed rescue
        plan = FaultPlan(seed=9, specs=[
            FaultSpec("fleet.replica_kill", "kill", at=2, count=1,
                      match="replica:0:")])
        with plan:
            reqs = wave(fleet)
        single.close()
        fleet.close()
        ok = all(r.done and not r.failed for r in reqs)
        if fleet.stats["failovers"] < 1 or not ok:
            print(f"# fleet failover bench: no replica death absorbed "
                  f"(failovers={fleet.stats['failovers']}, ok={ok})",
                  flush=True)
        else:
            _emit("fleet_failover_time_s", fleet.stats["failover_s"],
                  f"s (journal load + re-admit + catch-up-to-hwm after a "
                  f"mid-wave replica kill; "
                  f"{fleet.stats['failover_requests']} request(s) failed "
                  f"over to 2 survivors)", None)

    # -- process-per-replica arm (inference/procfleet): real scale-out ----
    try:
        from paddle_tpu.inference.fleet import FleetConfig as _FC
        from paddle_tpu.inference.procfleet import (ProcFleetConfig,
                                                    ProcFleetRouter)

        # workers rebuild the CPU-sized engine in their own process with
        # their own host device — the separate-device claim this series
        # could never make in one process (TPU hosts pin workers to cpu:
        # two processes cannot share the chip)
        tiny_kw = dict(seed=0, num_hidden_layers=2, max_batch=2,
                       max_len=32, page_size=8, block_size=4,
                       prompt_buckets=[16])
        proc_cfg = ProcFleetConfig(
            factory="paddle_tpu.inference.procfleet.presets:"
                    "tiny_llama_engine",
            factory_kwargs=tiny_kw, env={"JAX_PLATFORMS": "cpu"})
        rng_p = np.random.default_rng(0)
        pprompts = [rng_p.integers(0, 256, (16,)).astype(np.int32)
                    for _ in range(12)]

        def proc_wave(target, n_new=16):
            reqs = [Request(p, max_new_tokens=n_new, seed=500 + i)
                    for i, p in enumerate(pprompts)]
            for r in reqs:
                target.submit(r)
            target.run_until_done(max_steps=20000)
            return reqs

        with tempfile.TemporaryDirectory() as ptmp:
            arms = {}
            for n_proc in (1, 2):
                pf = ProcFleetRouter(
                    proc_cfg, os.path.join(ptmp, f"proc{n_proc}"),
                    num_replicas=n_proc,
                    config=_FC(brownout_depth=10 ** 9,
                               parallel_step=n_proc > 1))
                try:
                    proc_wave(pf)           # compile every worker
                    dt = float("inf")
                    for _ in range(3):
                        t0 = _t.perf_counter()
                        proc_wave(pf)
                        dt = min(dt, _t.perf_counter() - t0)
                    arms[n_proc] = 12 * 16 / dt
                finally:
                    # a leaked worker (full jax runtime) would time-slice
                    # against every later bench on small hosts
                    pf.close()
        ncores = os.cpu_count() or 1
        _emit("fleet_proc_tokens_per_sec", arms[2],
              f"useful tok/s (2 worker PROCESSES, own jax runtime/model/"
              f"journal each, parallel_step; 1 worker process on the same "
              f"wave: {arms[1]:.0f} tok/s — the ratio is REAL scale-out "
              f"and needs >=2 free host cores to exceed 1: this host has "
              f"{ncores} core(s), so "
              f"{'the >=1.5x claim is measurable' if ncores >= 2 else 'two processes time-slice one core and the ratio reads wire overhead, not scale-out'})",
              arms[2] / arms[1])
    except Exception as e:  # secondary lines must never kill the primary
        _phase_failed("fleet proc", e)


def bench_serving_sharded(dev, on_tpu):
    """Mesh-sharded serving (docs/SERVING.md "Sharded serving").

    - ``serving_sharded_tokens_per_sec``: useful tok/s of the tp=2
      column-parallel engine over a mixed wave; vs_baseline = sharded /
      unsharded fused engine on the IDENTICAL wave (byte-identical
      streams by contract, so the ratio is pure overhead accounting). On
      a CPU host the mesh is two forced host devices, so the ratio reads
      collective + shard_map dispatch overhead (<=1 expected) — the
      SECONDARY guard catches that overhead blowing up, not a speedup
      claim. On a real TPU slice the same line reads weight/KV memory
      scale-out.
    - ``fleet_proc_sharded_tokens_per_sec``: the scale-OUT ratio at
      mesh=2 — 2 worker PROCESSES, each serving over its own private
      2-device group (spawned workers force their own host device
      count), vs ONE mesh=2 worker on the identical wave. Like its
      unsharded sibling the ratio rides host-core weather: >=1.5-2x
      expected on an idle >=4-core box, lower under CI contention.
      SECONDARY ("higher", wide tolerance).
    """
    import os
    import tempfile
    import time as _t

    import jax

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny()
    slots, max_len, page, block, n_req, max_new, plen = (
        4, 64, 8, 4, 8, 8, 16)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
               for _ in range(n_req)]
    useful = n_req * max_new

    def build(mesh=None):
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block,
            prefix_cache=PrefixCacheConfig(extra_blocks=slots),
            mesh=mesh)

    def wave(target):
        reqs = [Request(p, max_new_tokens=max_new, seed=500 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            target.add_request(r)
        target.run_until_done(max_steps=20000)

    def timed(target):
        t0 = _t.perf_counter()
        wave(target)
        return _t.perf_counter() - t0

    if len(jax.devices()) < 2:
        print("# serving sharded bench skipped: 1 device on this host",
              flush=True)
        return
    flat, sharded = build(), build(mesh=2)
    wave(flat)                          # compile both engines' programs
    wave(sharded)
    dt_flat = dt_sh = float("inf")
    for _ in range(3):                  # interleaved best-of-3
        dt_flat = min(dt_flat, timed(flat))
        dt_sh = min(dt_sh, timed(sharded))
    flat_tps, sh_tps = useful / dt_flat, useful / dt_sh
    census = {k: f"{v:.0f}B" for k, v in sharded._mesh_programs.items()}
    print(f"# serving sharded per-program collective census (wire bytes "
          f"per dispatch): {census}", flush=True)
    _emit("serving_sharded_tokens_per_sec", sh_tps,
          f"useful tok/s (tp=2 column-parallel shard_map engine, {slots} "
          f"slots, {n_req} reqs max_new {max_new}; unsharded fused engine "
          f"on the same wave: {flat_tps:.0f} tok/s — byte-identical "
          f"streams, the ratio is collective+dispatch overhead on CPU)",
          sh_tps / flat_tps)

    # -- process-per-replica arm at mesh=2: real scale-out ---------------
    try:
        from paddle_tpu.inference.fleet import FleetConfig as _FC
        from paddle_tpu.inference.procfleet import (ProcFleetConfig,
                                                    ProcFleetRouter)

        proc_cfg = ProcFleetConfig(
            factory="paddle_tpu.inference.procfleet.presets:"
                    "tiny_llama_mesh_engine",
            factory_kwargs=dict(seed=0, num_hidden_layers=2, max_len=32,
                                page_size=8, block_size=4,
                                prompt_buckets=[16]),
            env={"JAX_PLATFORMS": "cpu"}, mesh=2)
        rng_p = np.random.default_rng(0)
        pprompts = [rng_p.integers(0, 256, (16,)).astype(np.int32)
                    for _ in range(12)]

        def proc_wave(target):
            reqs = [Request(p, max_new_tokens=16, seed=500 + i)
                    for i, p in enumerate(pprompts)]
            for r in reqs:
                target.submit(r)
            target.run_until_done(max_steps=20000)

        with tempfile.TemporaryDirectory() as ptmp:
            arms = {}
            for n_proc in (1, 2):
                pf = ProcFleetRouter(
                    proc_cfg, os.path.join(ptmp, f"mesh{n_proc}"),
                    num_replicas=n_proc,
                    config=_FC(brownout_depth=10 ** 9,
                               parallel_step=n_proc > 1))
                try:
                    proc_wave(pf)       # compile every worker
                    dt = float("inf")
                    for _ in range(3):
                        t0 = _t.perf_counter()
                        proc_wave(pf)
                        dt = min(dt, _t.perf_counter() - t0)
                    arms[n_proc] = 12 * 16 / dt
                finally:
                    pf.close()
        ncores = os.cpu_count() or 1
        ratio = arms[2] / arms[1]
        print(f"# fleet mesh=2 scale-out: 2 workers x 2-device groups "
              f"{arms[2]:.0f} tok/s vs 1 worker {arms[1]:.0f} tok/s = "
              f"{ratio:.2f}x ({ncores} host core(s); >=1.5-2x expected "
              f"on an idle multi-core box)", flush=True)
        _emit("fleet_proc_sharded_tokens_per_sec", arms[2],
              f"useful tok/s (2 worker PROCESSES at mesh tp=2, each over "
              f"its own private 2-device group; 1 mesh=2 worker on the "
              f"same wave: {arms[1]:.0f} tok/s)", ratio)
    except Exception as e:  # secondary lines must never kill the primary
        _phase_failed("fleet sharded proc", e)


def bench_observability(dev, on_tpu):
    """Observability envelope (docs/OBSERVABILITY.md): TTFT SLO
    percentiles and the cost of full instrumentation.

    - ``serving_p50/p99_time_to_first_token_ms``: submit -> first
      scheduled token over a mixed serving wave with more requests than
      slots (queue wait included), computed from the TraceRecorder's
      fixed-bucket histograms over the WARM waves only (a fresh recorder
      is attached after the compile wave — compile-time TTFT is operator
      cost, not an SLO). SECONDARY-guarded ("lower"): ROADMAP item 2's
      speculative-decode work must move these down, not up.
    - ``observability_overhead_pct``: identical warm wave on a bare
      engine vs one with full metrics + tracing attached (TraceRecorder
      into a MetricsRegistry with the engine collector registered and a
      live MetricsServer thread). The contract is the same as
      ``guard_overhead_pct``: all recording is host-side, buffered and
      off the step path. On CPU tiny models the read is NOISY (sub-ms
      steps make fixed host costs loom; interleaved best-of-3 still
      swings roughly -15%..+15% run to run) — like guard_overhead_pct,
      only the relative regression vs the recorded baseline matters,
      and the SECONDARY guard floors the baseline at 5% before the 2x
      comparison.
    """
    import time as _t

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import (MetricsRegistry, MetricsServer,
                                          TraceRecorder, engine_collector)

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=512,
            dtype="bfloat16")
        slots, max_len, page, block, n_req, max_new, plen = (
            4, 256, 16, 8, 12, 48, 16)
    else:
        cfg = LlamaConfig.tiny()
        slots, max_len, page, block, n_req, max_new, plen = (
            2, 32, 8, 4, 8, 8, 8)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
               for _ in range(n_req)]

    def make(tracer=None):
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block, prefix_cache=True, tracer=tracer)

    registry = MetricsRegistry()
    plain = make()
    traced = make(TraceRecorder(registry=registry))
    registry.register_collector(engine_collector(traced))
    server = MetricsServer(registry, port=0)   # live endpoint, not scraped
    #                                            inside the timed windows

    def wave(e):
        reqs = [Request(p, max_new_tokens=max_new, seed=700 + i)
                for i, p in enumerate(prompts)]
        for r in reqs:
            e.add_request(r)
        e.run_until_done(max_steps=20000)

    def timed(e):
        t0 = _t.perf_counter()
        wave(e)
        return _t.perf_counter() - t0

    try:
        wave(plain)                    # compile both engines' programs
        wave(traced)
        # WARM-only SLO: swap in a fresh recorder so compile-wave TTFT
        # (whole seconds of jit) doesn't pollute the percentiles
        tracer = TraceRecorder()   # private registry — warm-wave SLO only
        traced.tracer = tracer
        dt_plain = dt_traced = float("inf")
        for _ in range(3):             # interleaved best-of-3 (chip-state
            dt_plain = min(dt_plain, timed(plain))  # drift hits both)
            dt_traced = min(dt_traced, timed(traced))
        pct = (dt_traced - dt_plain) / dt_plain * 100.0
        slo = tracer.slo_summary()
        scrape = registry.dump()
    finally:
        # a failed wave must not leak the endpoint thread/port into the
        # rest of the bench run (main() catches and moves on)
        server.close()
    print(f"# observability scrape: {scrape.count('# TYPE')} metric "
          f"families, {len(tracer.events)} trace events over "
          f"{slo['submitted']} warm requests", flush=True)
    _emit("serving_p50_time_to_first_token_ms",
          slo["p50_time_to_first_token_ms"],
          f"ms (warm waves, {n_req} reqs on {slots} slots incl. queue "
          f"wait, prompt {plen} max_new {max_new}, prefix cache on)", None)
    _emit("serving_p99_time_to_first_token_ms",
          slo["p99_time_to_first_token_ms"],
          f"ms (warm waves, {n_req} reqs on {slots} slots incl. queue "
          f"wait, prompt {plen} max_new {max_new}, prefix cache on)", None)
    _emit("observability_overhead_pct", pct,
          f"% (full tracing + metrics registry + live endpoint vs bare "
          f"engine, identical warm wave best-of-3, {n_req} reqs "
          f"{slots} slots)", None)


def bench_slo_burst(dev, on_tpu):
    """SLO observatory under open-loop burst traffic (docs/OBSERVABILITY.md
    "Traffic replay & SLO attainment"; ROADMAP items 3/5's
    ``serving_ttft_p99_under_burst_ms``).

    A seeded burst schedule (observability/workload.py: Poisson arrivals
    with a square-wave rate multiplier, lognormal prompt/output lengths,
    two tenants sharing a system prefix) replays WALL-CLOCK open-loop
    against a 2-replica fleet — arrivals never wait for the server, so
    burst backlogs produce real queueing tails. All three lines are
    SECONDARY-guarded (tools/check_bench_regression.py):

    - ``serving_slo_attainment_pct`` ("higher"): % of finished requests
      meeting the TTFT target — collapses when the serving path grows
      latency or sheds wholesale.
    - ``serving_goodput_tokens_per_sec`` ("higher"): tokens/s from
      SLO-meeting requests only, as distinct from raw throughput (a
      collapsed server can post throughput with ~0 goodput).
    - ``serving_ttft_p99_under_burst_ms`` ("lower", 250ms floor): the
      tail the open-loop arrivals exist to expose; CPU tiny reads are
      noisy, so only a >2x regression past the floor fails.
    """
    from paddle_tpu.inference.fleet import FleetConfig, FleetRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import (ReplayDriver, SLOConfig,
                                          SLOMonitor, TenantSpec,
                                          TraceRecorder, WorkloadConfig,
                                          generate_schedule)
    import tempfile
    import time as _t

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=512,
            dtype="bfloat16")
        slots, max_len, page, block = 4, 256, 16, 8
        wl = WorkloadConfig(
            seed=23, duration_s=6.0, rate_rps=6.0, arrival="burst",
            burst_every_s=3.0, burst_len_s=1.0, burst_multiplier=4.0,
            vocab_size=cfg.vocab_size, prompt_min=16, prompt_max=48,
            output_min=8, output_max=32,
            tenants=(TenantSpec("chat", 2.0, prefix_len=16),
                     TenantSpec("batch", 1.0, priority=2)))
        ttft_ms = 1500.0
    else:
        cfg = LlamaConfig.tiny(num_hidden_layers=1)
        slots, max_len, page, block = 2, 32, 8, 2
        wl = WorkloadConfig(
            seed=23, duration_s=3.0, rate_rps=8.0, arrival="burst",
            burst_every_s=1.5, burst_len_s=0.5, burst_multiplier=3.0,
            vocab_size=cfg.vocab_size, prompt_min=4, prompt_max=16,
            output_min=2, output_max=8,
            tenants=(TenantSpec("chat", 2.0, prefix_len=8),
                     TenantSpec("batch", 1.0, priority=2)))
        ttft_ms = 500.0
    model = LlamaForCausalLM(cfg)

    def build():
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block, prefix_cache=True)

    schedule = generate_schedule(wl)
    with tempfile.TemporaryDirectory() as tmp:
        tracer = TraceRecorder()
        fleet = FleetRouter(build, tmp, num_replicas=2, tracer=tracer,
                            config=FleetConfig(brownout_depth=10 ** 9))
        # compile wave (closed loop), then a FRESH recorder+monitor so
        # compile-time TTFT never pollutes the measured percentiles —
        # the bench_observability warm-only discipline
        rng = np.random.default_rng(0)
        warm = [Request(rng.integers(0, cfg.vocab_size,
                                     (wl.prompt_min,)).astype(np.int32),
                        max_new_tokens=wl.output_max, seed=900 + i)
                for i in range(2 * slots)]
        for r in warm:
            fleet.submit(r)
        fleet.run_until_done(max_steps=20000)
        tracer = TraceRecorder()
        fleet.tracer = tracer
        for rep in fleet.replicas:
            rep.sup.tracer = tracer
            rep.sup._attach_tracer()
        monitor = SLOMonitor(SLOConfig(ttft_ms=ttft_ms, window_s=1.0),
                             tracer=tracer)
        driver = ReplayDriver(fleet, schedule, monitor=monitor,
                              wall_clock=True, max_steps=200000)
        t0 = _t.perf_counter()
        report = driver.run()
        wall = _t.perf_counter() - t0
        fleet.close()
    tot = report["slo"]["totals"]
    attain = (100.0 * tot["met"] / tot["finished"]
              if tot["finished"] else 0.0)
    goodput = tot["good_tokens"] / max(wall, 1e-9)
    p99 = tracer._h_ttft.quantile(0.99)
    print(f"# slo burst replay: {len(schedule)} arrivals over "
          f"{wl.duration_s}s schedule, {report['driver']['steps']} fleet "
          f"steps in {wall:.2f}s wall, refused "
          f"{report['driver']['refused']}", flush=True)
    _emit("serving_slo_attainment_pct", attain,
          f"% of {tot['finished']} finished requests meeting TTFT<="
          f"{ttft_ms:.0f}ms (2-replica fleet, open-loop burst "
          f"{wl.rate_rps}x{wl.burst_multiplier} rps, prefix cache on)",
          None)
    _emit("serving_goodput_tokens_per_sec", goodput,
          f"tok/s from SLO-meeting requests only ({tot['good_tokens']} of "
          f"{tot['tokens']} tokens; raw {tot['tokens'] / max(wall, 1e-9):.0f}"
          f" tok/s)", None)
    if p99 is None:
        # no first token was ever scheduled: emitting 0.0 would read as a
        # perfect lower-is-better line (and poison the recorded baseline);
        # absence passes the SECONDARY guard vacuously instead
        print("# slo burst bench: no first tokens recorded — "
              "serving_ttft_p99_under_burst_ms omitted", flush=True)
    else:
        _emit("serving_ttft_p99_under_burst_ms", p99,
              f"ms (p99 TTFT over the open-loop burst replay, queue wait "
              f"included, {tot['finished']} requests on 2x{slots} slots)",
              None)


def bench_disagg(dev, on_tpu):
    """Disaggregated prefill/decode tiers under burst traffic
    (docs/SERVING.md "Disaggregated tiers"; ROADMAP item 3). A/B: the
    PR 11 bursty open-loop ``generate_schedule`` mix replayed wall-clock
    against a UNIFIED 2-replica fleet, then against a TieredRouter with 1
    prefill + 1 decode replica (same engine config, same device, same
    schedule bytes) — the tier split packs prompts on the prefill replica
    and migrates finished chains, so decode never stalls behind a long
    prompt. Both emitted lines are SECONDARY-guarded
    (tools/check_bench_regression.py):

    - ``serving_disagg_ttft_p99_under_burst_ms`` ("lower", 250ms floor):
      p99 TTFT of the tiered arm; the unified arm's p99 prints as a
      comment for the A/B read.
    - ``serving_kv_migration_time_s`` ("lower", 0.5s floor): mean
      export -> splice wall time per migrated chain.
    """
    import os
    import tempfile

    from paddle_tpu.inference.disagg import TieredRouter
    from paddle_tpu.inference.fleet import FleetConfig, FleetRouter
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, Request)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.observability import (ReplayDriver, TenantSpec,
                                          TraceRecorder, WorkloadConfig,
                                          generate_schedule)

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, max_position_embeddings=512,
            dtype="bfloat16")
        slots, max_len, page, block = 4, 256, 16, 8
        wl = WorkloadConfig(
            seed=29, duration_s=6.0, rate_rps=6.0, arrival="burst",
            burst_every_s=3.0, burst_len_s=1.0, burst_multiplier=4.0,
            vocab_size=cfg.vocab_size, prompt_min=16, prompt_max=48,
            output_min=8, output_max=32,
            tenants=(TenantSpec("chat", 2.0, prefix_len=16),
                     TenantSpec("batch", 1.0, priority=2)))
    else:
        cfg = LlamaConfig.tiny(num_hidden_layers=1)
        slots, max_len, page, block = 2, 32, 8, 2
        wl = WorkloadConfig(
            seed=29, duration_s=3.0, rate_rps=8.0, arrival="burst",
            burst_every_s=1.5, burst_len_s=0.5, burst_multiplier=3.0,
            vocab_size=cfg.vocab_size, prompt_min=4, prompt_max=16,
            output_min=2, output_max=8,
            tenants=(TenantSpec("chat", 2.0, prefix_len=8),
                     TenantSpec("batch", 1.0, priority=2)))
    model = LlamaForCausalLM(cfg)

    def build():
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=block,
            prefix_cache=PrefixCacheConfig(extra_blocks=slots))

    schedule = generate_schedule(wl)
    rng = np.random.default_rng(0)
    warm = [Request(rng.integers(0, cfg.vocab_size,
                                 (wl.prompt_min,)).astype(np.int32),
                    max_new_tokens=wl.output_max, seed=950 + i)
            for i in range(2 * slots)]

    def replay(target):
        """Warm (compile) wave closed-loop, then a FRESH recorder — and a
        migration-stats snapshot — for the measured open-loop replay: the
        warm-only SLO discipline, applied to TTFT *and* to
        serving_kv_migration_time_s (the warm wave's migrations carry
        first-call jit/dispatch cost and must not pollute the mean)."""
        for r in warm:
            target.submit(Request(r.prompt, max_new_tokens=r.max_new_tokens,
                                  seed=r.seed))
        target.run_until_done(max_steps=20000)
        tracer = TraceRecorder()
        target.tracer = tracer
        for rep in target.replicas:
            rep.sup.tracer = tracer
            rep.sup._attach_tracer()
        snap = {k: target.stats.get(k, 0) for k in
                ("migrations", "migration_s", "migration_pages",
                 "migration_bytes", "migration_deferred",
                 "migration_refused", "migration_reprefill")}
        driver = ReplayDriver(target, schedule, wall_clock=True,
                              max_steps=200000)
        driver.run()
        return tracer, snap

    with tempfile.TemporaryDirectory() as tmp:
        unified = FleetRouter(build, os.path.join(tmp, "uni"),
                              num_replicas=2,
                              config=FleetConfig(brownout_depth=10 ** 9))
        tr_uni, _ = replay(unified)
        unified.close()
        tiered = TieredRouter(build, build, os.path.join(tmp, "tier"),
                              num_prefill=1, num_decode=1,
                              config=FleetConfig(brownout_depth=10 ** 9))
        tr_tier, snap = replay(tiered)
        tiered.close()
    p99_uni = tr_uni._h_ttft.quantile(0.99)
    p99_tier = tr_tier._h_ttft.quantile(0.99)
    # measured-window deltas only: the warm wave's migrations are compile
    # cost, not steady-state handoff time
    mig = tiered.stats["migrations"] - snap["migrations"]
    mig_s = tiered.stats["migration_s"] - snap["migration_s"]
    mig_pages = tiered.stats["migration_pages"] - snap["migration_pages"]
    mig_bytes = tiered.stats["migration_bytes"] - snap["migration_bytes"]
    print(f"# disagg burst A/B: {len(schedule)} arrivals; unified p99 TTFT "
          f"{p99_uni if p99_uni is None else round(p99_uni, 1)}ms vs tiered "
          f"{p99_tier if p99_tier is None else round(p99_tier, 1)}ms; "
          f"{mig} chain(s) migrated in the measured window "
          f"({mig_pages} pages, "
          f"{tiered.stats['migration_deferred'] - snap['migration_deferred']}"
          f" deferred step(s), "
          f"{tiered.stats['migration_refused'] - snap['migration_refused']}"
          f" splice refusal(s), "
          f"{tiered.stats['migration_reprefill'] - snap['migration_reprefill']}"
          f" re-prefills)", flush=True)
    if p99_tier is None:
        print("# disagg bench: no first tokens recorded — "
              "serving_disagg_ttft_p99_under_burst_ms omitted", flush=True)
    else:
        _emit("serving_disagg_ttft_p99_under_burst_ms", p99_tier,
              f"ms (p99 TTFT, open-loop burst replay on 1-prefill+"
              f"1-decode tiers, {slots} slots each; unified 2-replica "
              f"fleet on the same schedule: "
              f"{p99_uni if p99_uni is None else round(p99_uni, 1)}ms)",
              None)
    if mig:
        _emit("serving_kv_migration_time_s", mig_s / mig,
              f"s (mean export->splice wall time per migrated chain, warm "
              f"measured window only; {mig} migration(s), "
              f"{mig_bytes} bytes moved)", None)
    else:
        print("# disagg bench: no chain migrated — "
              "serving_kv_migration_time_s omitted", flush=True)


def bench_serving_migration_under_loss(dev, on_tpu):
    """KV-migration tail under seeded wire loss (docs/SERVING.md
    "Transport seam"; ISSUE 17). A/B on a loopback-transport
    ProcTieredRouter (1 prefill + 2 decode, workers are threads in this
    process): the same request wave runs once on a CLEAN chaos-wrapped
    wire, then under a seeded FaultPlan that DROPS one MIGRATE_IN frame
    and BITFLIPS the KV payload of another (re-framed, so only the
    end-to-end per-page crc32 catches it) — the drill pair
    ``net_flaky_migration`` proves byte-identity; this line prices it.
    Recovery (payload-sized timeout -> hedged re-splice under a stable
    idempotence key, typed KVChainCorrupt refusal -> retry elsewhere)
    stays ON in both arms so the delta is injected loss, not feature
    overhead. Emits ``serving_migration_under_loss_p99_s``: p99
    export -> splice wall time per migrated chain in the lossy arm
    (clean-arm p99 prints as a comment for the A/B read), SECONDARY-
    guarded with a floor sized to the hedge timeout so CPU weather
    cannot flap it."""
    import tempfile

    from paddle_tpu.distributed.resilience import FaultPlan, FaultSpec
    from paddle_tpu.inference.procfleet import (ProcFleetConfig,
                                                ProcTieredRouter)
    from paddle_tpu.inference.serving import Request
    from paddle_tpu.models import LlamaConfig

    vocab = LlamaConfig.tiny().vocab_size
    op_timeout_s = 5.0

    def cfg():
        return ProcFleetConfig(
            factory="paddle_tpu.inference.procfleet.presets:"
                    "tiny_llama_prefix_engine",
            factory_kwargs={"seed": 11}, transport="loopback",
            chaos=True, op_timeout_s=op_timeout_s, hedge=True,
            verify_crc=True)

    def wave(seed0):
        rng = np.random.default_rng(47)
        return [Request(rng.integers(0, vocab, (6,)).astype(np.int32),
                        max_new_tokens=8, seed=seed0 + i)
                for i in range(8)]

    def run(tiered, reqs, plan=None):
        """One wave to completion; returns the migration samples it
        added (per-chain export -> splice wall time, hedge wait
        included)."""
        n0 = len(tiered.migration_samples)
        ctx = plan if plan is not None else contextlib.nullcontext()
        with ctx:
            for r in reqs:
                tiered.submit(r)
            tiered.run_until_done(max_steps=800)
        if any(r.failed or not r.done for r in reqs):
            raise RuntimeError("migration-under-loss wave lost requests")
        return list(tiered.migration_samples[n0:])

    def arm(plan=None):
        """Fresh router per arm (engines re-pay jit compile — the warm
        wave eats it so the measured wave prices steady-state handoff,
        and the faulted wave never times out on a compile)."""
        with tempfile.TemporaryDirectory() as tmp:
            tiered = ProcTieredRouter(cfg(), cfg(), tmp,
                                      num_prefill=1, num_decode=2)
            try:
                run(tiered, wave(970))                      # warm/compile
                samples = run(tiered, wave(990), plan=plan)
                return samples, dict(tiered.stats)
            finally:
                tiered.close()

    clean, _ = arm()
    plan = FaultPlan(seed=7, specs=[
        FaultSpec("net.send", "drop", at=1, count=1, match="MIGRATE_IN"),
        FaultSpec("net.send", "bitflip", at=4, count=1, arg=64,
                  match="MIGRATE_IN")])
    lossy, stats = arm(plan)
    fired = sorted(a for (_, _, a) in plan.log)
    p99_clean = float(np.percentile(clean, 99)) if clean else None
    print(f"# migration-under-loss A/B: clean wire "
          f"{len(clean)} migration(s) p99 "
          f"{None if p99_clean is None else round(p99_clean, 3)}s; lossy "
          f"wire {len(lossy)} migration(s), faults fired {fired}, "
          f"{stats['migration_hedges']} hedge(s), "
          f"{stats['migration_corrupt']} typed refusal(s), "
          f"{stats['migration_reprefill']} reprefill(s)", flush=True)
    if not lossy or len(fired) < 2:
        print("# migration-under-loss bench: faulted wave migrated "
              "nothing (or faults never fired) — "
              "serving_migration_under_loss_p99_s omitted", flush=True)
        return
    _emit("serving_migration_under_loss_p99_s",
          float(np.percentile(lossy, 99)),
          f"s (p99 export->splice per migrated chain with a seeded "
          f"MIGRATE_IN drop + CRC-valid bitflip on the wire, hedged "
          f"recovery on; clean-wire p99 "
          f"{None if p99_clean is None else round(p99_clean, 3)}s)",
          None)


def bench_speculative(dev, on_tpu):
    """Speculative multi-token decoding + int8 paged-KV A/B (docs/
    SERVING.md "Speculative decode" / "int8 KV cache"; ROADMAP item 2).
    All three lines SECONDARY-guarded (tools/check_bench_regression.py):

    - ``serving_spec_tokens_per_sec`` ("higher"): useful tok/s with the
      speculative verify mega-step on, over a repetitive (drafter-
      friendly) greedy wave; the spec-off twin runs the SAME wave and
      prints as a comment — the A/B read. Streams are asserted
      byte-identical before any timing is believed.
    - ``serving_spec_acceptance_rate`` ("higher"): accepted / proposed
      draft tokens over the timed waves.
    - ``serving_int8_kv_slots_headroom`` ("higher"): pool blocks
      affordable at EQUAL bytes when the pool is int8 (pages + scales)
      instead of the parameter dtype — the slots / radix-reach multiplier
      of the block format (~2x at bf16, ~4x at f32). Computed from the
      live pools' actual array bytes, and the int8 engine runs the wave
      to prove the format serves end to end.
    """
    import time as _t

    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig, Request,
                                              SpecConfig)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16")
        slots, motif_len, reps, max_new, page, k = 8, 8, 8, 64, 16, 4
    else:
        cfg = LlamaConfig.tiny(num_hidden_layers=1)
        slots, motif_len, reps, max_new, page, k = 4, 4, 6, 24, 8, 4
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prompt_len = motif_len * reps
    # repetitive prompts (shared motif per request): the self-speculative
    # n-gram drafter's target workload — few-shot / template serving
    prompts = [np.tile(rng.integers(0, cfg.vocab_size,
                                    (motif_len,)).astype(np.int32), reps)
               for _ in range(2 * slots)]
    new_toks = [(i % 4 + 1) * max_new // 4 for i in range(len(prompts))]
    useful = sum(new_toks)
    max_len = prompt_len + max_new

    def build(**kw):
        return ContinuousBatchingEngine(
            model, max_batch=slots, max_len=max_len, page_size=page,
            block_size=4,
            prefix_cache=PrefixCacheConfig(extra_blocks=slots), **kw)

    def run_wave(e):
        reqs = [Request(p, max_new_tokens=n)
                for p, n in zip(prompts, new_toks)]
        for r in reqs:
            e.add_request(r)
        e.run_until_done(max_steps=40000)
        return [list(r.tokens) for r in reqs]

    def timed(fn, *a):
        t0 = _t.perf_counter()
        fn(*a)
        return _t.perf_counter() - t0

    base = build()
    spec = build(speculative=SpecConfig(k=k))
    ref_streams = run_wave(base)               # compile + prime radix
    spec_streams = run_wave(spec)
    if spec_streams != ref_streams:
        print("# bench_speculative: SPEC STREAMS DIVERGED from the "
              "non-speculative engine — timings withheld", flush=True)
        return
    p0, a0 = spec.stats["spec_proposed"], spec.stats["spec_accepted"]
    dt_base = dt_spec = float("inf")
    for _ in range(3):                         # best-of-3, interleaved
        dt_base = min(dt_base, timed(run_wave, base))
        dt_spec = min(dt_spec, timed(run_wave, spec))
    proposed = spec.stats["spec_proposed"] - p0
    accepted = spec.stats["spec_accepted"] - a0
    acc_rate = accepted / max(1, proposed)
    print(f"# speculative A/B: spec-off {useful / dt_base:.0f} useful "
          f"tok/s vs spec-on {useful / dt_spec:.0f} (k={k}, "
          f"{spec.stats['spec_steps']} verify dispatches, streams "
          f"byte-identical)", flush=True)
    _emit("serving_spec_tokens_per_sec", useful / dt_spec,
          f"useful tok/s (speculative k={k} verify mega-step, {slots} "
          f"slots, repetitive prompt {prompt_len}, max_new "
          f"{max_new // 4}-{max_new}; spec-off twin on the same wave: "
          f"{useful / dt_base:.0f} tok/s)",
          (useful / dt_spec) / max(useful / dt_base, 1e-9))
    _emit("serving_spec_acceptance_rate", acc_rate,
          f"accepted/proposed draft tokens (timed waves: {accepted}/"
          f"{proposed}, n-gram drafter over prompt+generated ids)", None)

    # int8 arm: blocks affordable at equal bytes, from the live pools
    i8 = build(kv_cache="int8")
    i8_streams = run_wave(i8)                  # the format serves end to end
    served = all(len(s) == n for s, n in zip(i8_streams, new_toks))
    det = ("full wave served" if served
           else "WAVE TRUNCATED — int8 serving path broken")

    def pool_bytes(e):
        total = 0
        for kp, vp in e.caches["kv"]:
            for side in (kp, vp):
                data = getattr(side, "data", side)
                total += data.size * data.dtype.itemsize
                scale = getattr(side, "scale", None)
                if scale is not None:
                    total += scale.size * scale.dtype.itemsize
        return total

    blocks = i8._kv_quant_blocks or i8.caches["kv"][0][0].shape[0]
    headroom = pool_bytes(base) / max(1, pool_bytes(i8))
    _emit("serving_int8_kv_slots_headroom", headroom,
          f"x pool blocks at equal bytes (int8 pages + per-block scales "
          f"vs {cfg.dtype} pool, {blocks} blocks/layer-side; {det})",
          None)


def bench_unet(dev, on_tpu):
    """Stable-Diffusion-class UNet train step (BASELINE config #5: conv +
    cross-attention through the compiler path). One jitted
    value_and_grad+SGD step, bf16 params/activations (fp32 groupnorm
    statistics inside); reports latents/s."""
    import time as _t

    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.api import _collect_state, _Swap
    from paddle_tpu.models import UNet2DConditionModel, UNetConfig

    if on_tpu:
        cfg = UNetConfig(block_channels=(128, 256, 512), layers_per_block=2,
                         num_heads=8, cross_attention_dim=768,
                         dtype="bfloat16")
        b, hw, ctx_len, iters = 8, 32, 77, 8
    else:
        cfg = UNetConfig.tiny()
        b, hw, ctx_len, iters = 2, 16, 6, 2
    model = UNet2DConditionModel(cfg)
    _, tensors = _collect_state(model)
    params = [t._data for t in tensors]
    rng = np.random.default_rng(0)
    batch = {
        "sample": jnp.asarray(rng.standard_normal((b, 4, hw, hw)),
                              jnp.float32),
        "timesteps": jnp.asarray(rng.integers(0, 1000, (b,)), jnp.int32),
        "context": jnp.asarray(
            rng.standard_normal((b, ctx_len, cfg.cross_attention_dim)),
            jnp.float32),
        "noise": jnp.asarray(rng.standard_normal((b, 4, hw, hw)),
                             jnp.float32),
    }

    def loss_of(ps):
        with _Swap(tensors, ps):
            return model.loss_fn(batch)

    @jax.jit
    def step(ps):
        l, g = jax.value_and_grad(loss_of)(ps)
        return l, [p - 1e-4 * gg.astype(p.dtype) for p, gg in zip(ps, g)]

    loss, params = step(params)
    jax.device_get(loss)
    t0 = _t.perf_counter()
    for _ in range(iters):
        loss, params = step(params)
    jax.device_get(loss)
    dt = _t.perf_counter() - t0
    _emit("sd_unet_latents_per_sec", b * iters / dt,
          f"latents/s (UNet ch{cfg.block_channels} ctx {ctx_len}x"
          f"{cfg.cross_attention_dim}, {hw}x{hw} latents, {cfg.dtype} "
          f"fwd+bwd+sgd, loss {float(loss):.3f})", None)


def bench_vit(dev, on_tpu):
    """ViT-L/16 bf16 classification train step (BASELINE config #5's second
    model). One jitted value_and_grad+SGD step; reports images/s + MFU."""
    import time as _t

    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.api import _collect_state, _Swap
    from paddle_tpu.vision.models import ViTConfig, VisionTransformer, vit_l_16

    if on_tpu:
        model = vit_l_16(dtype="bfloat16")
        b, iters = 32, 8
    else:
        model = VisionTransformer(ViTConfig.tiny())
        b, iters = 4, 2
    cfg = model.config
    _, tensors = _collect_state(model)
    params = [t._data for t in tensors]
    n_params = sum(int(np.prod(t.shape)) for t in tensors)
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.standard_normal(
        (b, cfg.in_channels, cfg.image_size, cfg.image_size)),
        jnp.bfloat16 if on_tpu else jnp.float32)
    labels = jnp.asarray(rng.integers(0, cfg.num_classes, (b,)), jnp.int32)

    def loss_of(ps):
        with _Swap(tensors, ps):
            return model.loss_fn(imgs, labels)  # the model's canonical CE

    @jax.jit
    def step(ps):
        l, g = jax.value_and_grad(loss_of)(ps)
        return l, [p - 1e-4 * gg.astype(p.dtype) for p, gg in zip(ps, g)]

    loss, params = step(params)
    jax.device_get(loss)
    t0 = _t.perf_counter()
    for _ in range(iters):
        loss, params = step(params)
    jax.device_get(loss)
    dt = _t.perf_counter() - t0
    ips = b * iters / dt
    n_tok = cfg.num_patches + 1
    flops_per_img = 6.0 * n_params * n_tok + 12.0 * cfg.num_layers *         cfg.hidden_size * n_tok * n_tok
    mfu = ips * flops_per_img / _device_peak(dev)
    _emit("vit_l16_images_per_sec", ips,
          f"images/s (ViT-L/16 {n_params/1e6:.0f}M {cfg.dtype} "
          f"{cfg.image_size}px batch {b} fwd+bwd+sgd, loss "
          f"{float(loss):.3f}, mfu {mfu:.3f})", None)


def bench_moe(dev, on_tpu):
    """Mixtral-class MoE llama train step: 8 swiglu experts, top-2 GShard
    routing via the sparse scatter dispatch (the dense einsum dispatch OOMs
    at this token count — its one-hot buffers are O(n^2 k) in tokens).
    MFU is computed over ACTIVATED parameters (top-k of the expert FLOPs)."""
    import jax

    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        # dispatch stays "auto" (scatter at this shape): the round-5
        # interleaved A/B (benchmarks/moe_ab.py) measured the dropless
        # grouped alternatives SLOWER at E=8 — scatter 0.409 vs megablox-gmm
        # ragged 0.344 vs in-repo pgmm 0.294 activated-MFU (docs/MOE_AB.md)
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=2048,
            dtype="bfloat16", num_experts=8, moe_topk=2)
        batch, seq, iters = 8, 2048, 8
    else:
        cfg = LlamaConfig.tiny(num_experts=4, num_hidden_layers=2)
        batch, seq, iters = 2, 32, 2
    model = LlamaForCausalLM(cfg)
    eng = Engine(model, mesh=None, lr=1e-4, clip_norm=1.0)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
               for _ in range(iters)]
    loss = eng.step(batches[0], batches[0])
    jax.device_get(loss)
    loss = eng.step(batches[0], batches[0])
    jax.device_get(loss)
    t0 = time.perf_counter()
    for ids in batches:            # fresh batch each step — no memorization
        loss = eng.step(ids, ids)
    jax.device_get(loss)
    dt = time.perf_counter() - t0
    tok = batch * seq * iters / dt
    # real parameter count (config.num_params() assumes a dense FFN); the
    # activated count replaces the expert share with its top-k fraction
    n_total = sum(int(np.prod(p.shape)) for p in model.parameters())
    n_exp = sum(int(np.prod(p.shape)) for name, p in model.named_parameters()
                if ".experts." in name)
    n_act = n_total - n_exp * (1.0 - cfg.moe_topk / cfg.num_experts)
    fpt = 6.0 * n_act + 6.0 * cfg.num_hidden_layers * cfg.hidden_size * seq
    mfu = tok * fpt / _device_peak(dev)
    _emit("llama_moe_8x_tokens_per_sec", tok,
          f"tokens/s (MoE llama {n_total/1e6:.0f}M total / {n_act/1e6:.0f}M "
          f"activated, 8 experts top-2 scatter dispatch, bf16 seq{seq}, "
          f"loss {float(loss):.3f}, activated-mfu {mfu:.3f})", None)


def bench_guard(dev, on_tpu):
    """Numeric-guard overhead: guarded vs unguarded fused train step.

    The guard adds one on-device health word (aggregated nan/inf reductions
    + EMA spike state) and a scalar-predicated zero-apply to the jitted
    step — docs/NUMERIC_GUARD.md budgets it at noise level. Interleaved
    best-of-3 (same discipline as bench_serving) so chip-state drift hits
    both variants equally; guarded as a secondary gate in
    tools/check_bench_regression.py."""
    import jax

    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.framework.numeric_guard import GuardPolicy
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=2048,
            dtype="bfloat16")
        batch, seq, iters = 8, 1024, 8
    else:
        cfg = LlamaConfig.tiny()
        batch, seq, iters = 2, 32, 4
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
               for _ in range(iters)]

    def make(guard):
        eng = Engine(LlamaForCausalLM(cfg), mesh=None, lr=1e-4,
                     clip_norm=1.0, guard=guard)
        jax.device_get(eng.step(batches[0], batches[0]))   # compile
        return eng

    def wave(eng):
        t0 = time.perf_counter()
        for ids in batches:
            loss = eng.step(ids, ids)
        jax.device_get(loss)
        return time.perf_counter() - t0

    plain, guarded = make(None), make(GuardPolicy())
    dt_plain = dt_guard = float("inf")
    for _ in range(3):
        dt_plain = min(dt_plain, wave(plain))
        dt_guard = min(dt_guard, wave(guarded))
    pct = (dt_guard - dt_plain) / dt_plain * 100.0
    n_params = cfg.num_params()
    _emit("guard_overhead_pct", pct,
          f"% (guarded vs unguarded fused step, llama {n_params/1e6:.0f}M "
          f"seq{seq} batch {batch}, {iters} steps best-of-3)", None)


def main():
    import jax

    from paddle_tpu.models import LlamaConfig

    from paddle_tpu.framework.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        raise SystemExit(
            f"bench.py measures the chip; jax found platform "
            f"{dev.platform!r} ({dev.device_kind}) — nothing is timed on it")
    _device_peak(dev)   # an unknown device_kind stops the run before it starts

    import gc

    try:
        bench_resnet(dev, on_tpu)
    except Exception as e:  # secondary lines must never kill the primary
        _phase_failed("resnet", e)
    gc.collect()
    try:
        bench_bert(dev, on_tpu)
    except Exception as e:
        _phase_failed("bert", e)
    gc.collect()
    try:
        bench_serving(dev, on_tpu)
    except Exception as e:
        _phase_failed("serving", e)
    gc.collect()
    try:
        bench_serving_large_batch(dev, on_tpu)
    except Exception as e:
        _phase_failed("serving large-batch", e)
    gc.collect()
    try:
        bench_serving_recovery(dev, on_tpu)
    except Exception as e:
        _phase_failed("serving recovery", e)
    gc.collect()
    try:
        bench_serving_mesh_degrade(dev, on_tpu)
    except Exception as e:
        _phase_failed("serving mesh degrade", e)
    gc.collect()
    try:
        bench_checkpoint_publish(dev, on_tpu)
    except Exception as e:
        _phase_failed("checkpoint publish", e)
    gc.collect()
    try:
        bench_fleet(dev, on_tpu)
    except Exception as e:
        _phase_failed("fleet", e)
    gc.collect()
    try:
        bench_observability(dev, on_tpu)
    except Exception as e:
        _phase_failed("observability", e)
    gc.collect()
    try:
        bench_slo_burst(dev, on_tpu)
    except Exception as e:
        _phase_failed("slo burst", e)
    gc.collect()
    try:
        bench_disagg(dev, on_tpu)
    except Exception as e:
        _phase_failed("disagg", e)
    gc.collect()
    try:
        bench_serving_migration_under_loss(dev, on_tpu)
    except Exception as e:
        _phase_failed("migration-under-loss", e)
    gc.collect()
    try:
        bench_speculative(dev, on_tpu)
    except Exception as e:
        _phase_failed("speculative", e)
    gc.collect()
    try:
        bench_serving_sharded(dev, on_tpu)
    except Exception as e:
        _phase_failed("serving sharded", e)
    gc.collect()
    try:
        bench_unet(dev, on_tpu)
    except Exception as e:
        _phase_failed("unet", e)
    gc.collect()
    try:
        bench_vit(dev, on_tpu)
    except Exception as e:
        _phase_failed("vit", e)
    gc.collect()
    try:
        bench_moe(dev, on_tpu)
    except Exception as e:
        _phase_failed("moe", e)
    gc.collect()
    try:
        bench_guard(dev, on_tpu)
    except Exception as e:
        _phase_failed("guard", e)
    gc.collect()

    if on_tpu:
        # legacy round-1 comparison config (MHA, no remat, seq 2048)
        legacy = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16", recompute=False)
        try:
            bench_llama("llama_750M_seq2048_tokens_per_sec", legacy,
                        batch=4, seq=2048, iters=8, dev=dev)
        except Exception as e:
            _phase_failed("legacy llama", e)
        gc.collect()

        # secondary: the round-2 north-star operating point (batch 4, remat
        # ON) kept for continuity/regression comparison. Round 5: the
        # flash_qkv policy additionally saves rope'd q/k/v (~1.6G at this
        # shape), killing the qkv-proj+rope+norm1 recompute — measured
        # remat tax 15.5% -> 10.7% vs no-remat in-process (benchmarks/
        # remat_ab.py)
        ns_remat = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            dtype="bfloat16", recompute=True, remat_policy="flash_qkv")
        try:
            bench_llama("llama_853M_seq4096_remat_tokens_per_sec", ns_remat,
                        batch=4, seq=4096, iters=8, dev=dev)
        except Exception as e:
            _phase_failed("remat llama", e)
        gc.collect()

        # long-context line: seq 16k single chip — possible since the flash
        # fwd/dq kernels stream K/V through the grid (HBM-bound, not
        # VMEM-bound). b1 no-remat fits (fused CE; measured faster than
        # remat: 0.51 vs 0.49 MFU)
        lc = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=16384,
            dtype="bfloat16", recompute=False)
        try:
            bench_llama("llama_672M_seq16k_tokens_per_sec", lc,
                        batch=1, seq=16384, iters=6, dev=dev)
        except Exception as e:
            _phase_failed("long-context llama", e)
        gc.collect()

        # seq-32k single chip (round 5): the streamed flash kernels + the
        # flash_qkv selective remat make 32k TRAINING fit one 16GB chip at
        # 0.54 MFU (the reference has no single-device 32k training path)
        lc32 = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=32768,
            dtype="bfloat16", recompute=True, remat_policy="flash_qkv")
        try:
            bench_llama("llama_672M_seq32k_tokens_per_sec", lc32,
                        batch=1, seq=32768, iters=4, dev=dev)
        except Exception as e:
            _phase_failed("seq-32k llama", e)
        gc.collect()

        # NORTH STAR (printed last — primary line): seq 4096, GQA 4:1,
        # ~850M params — the BASELINE.json 7B-class training shape, honestly
        # measured. Round-3 operating point: batch 2 WITHOUT remat — the
        # fused chunked CE freed the logits memory, so full activations fit
        # and the ~13% recompute tax is gone (model FLOPs == hardware FLOPs;
        # measured 0.59 -> ~0.66 MFU vs the batch-4 remat point above at
        # LOWER tokens/s). fp32 AdamW state 6.8G + bf16 params/grads 3.4G +
        # activations ~5G on the 16G chip.
        ns = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=4, max_position_embeddings=4096,
            dtype="bfloat16", recompute=False)
        bench_llama("llama_pretrain_tokens_per_sec_per_chip", ns,
                    batch=2, seq=4096, iters=8, dev=dev)
    if _FAILED:
        raise SystemExit(f"bench.py: {len(_FAILED)} phase(s) failed: "
                         f"{', '.join(_FAILED)}")


if __name__ == "__main__":
    main()
