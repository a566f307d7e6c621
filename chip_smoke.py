#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the trainer and the server once each through the entry points a user
calls (``Engine.step``, ``ContinuousBatchingEngine.run_until_done``), at the
full width of the models ``bench.py`` times, with seeded random weights, and
exits non-zero on the first thing that is not true. On a TPU it ends with one
JSON line, ``{"ok": true, "device": {...}}``; anywhere else it refuses before
building a model and prints no result.

One process holds the chip at a time: this parent imports nothing that
touches jax and runs one child per phase, each under a time limit, so a hung
kernel is a named failure and the trainer's memory is gone before the server
is built.

    python chip_smoke.py

Phases (children call ``run_phase``):
  train      853M llama (the north-star shape), a few fused steps on one
             fixed batch; flash kernels counted in the lowered step.
  serve      750M-class llama under the engine without and with a prefix
             cache; the same 16-request wave twice; paged-decode kernels
             counted in the lowered decode program; stream identity between
             the two and engine vs generate() reported, not asserted.
  kernels    flash fwd + grad and paged decode against their jnp references.
  multichip  only with >= 4 devices: the trainer on fsdp2 x tp2, the
             prefix-cache server on tp=4, placement and memory spread asserted.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 0

# --- trainer: bench.py's north-star point (853M, seq 4096, batch 2, no remat)
TRAIN_MODEL = dict(
    vocab_size=32000, hidden_size=2048, intermediate_size=5632,
    num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
    max_position_embeddings=4096, dtype="bfloat16", recompute=False)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 2, 4096, 6, 3e-4
#: fwd + dq + dkv flash kernels per layer
TRAIN_KERNEL_CALLS = 3 * TRAIN_MODEL["num_hidden_layers"]

# --- server: bench.py's serving point (750M-class, 8 slots, page 16, block 16)
SERVE_MODEL = dict(
    vocab_size=32000, hidden_size=2048, intermediate_size=5632,
    num_hidden_layers=12, num_attention_heads=16, num_key_value_heads=16,
    max_position_embeddings=2048, dtype="bfloat16")
SERVE_SLOTS, SERVE_MAX_LEN, SERVE_PAGE, SERVE_BLOCK = 8, 128, 16, 16
WAVE_REQUESTS, WAVE_PROMPT, WAVE_SHARED, WAVE_MAX_NEW = 16, 64, 48, 64
#: one paged-decode kernel per layer inside the decode scan body
SERVE_KERNEL_CALLS = SERVE_MODEL["num_hidden_layers"]

#: per-phase wall limits, about twice what each took cold on the v5e
#: (73 / 345 / 50 s; multichip 406 s); the one-chip phases sum to under
#: the 1200 s the whole run is allowed
PHASE_TIMEOUT_S = {"train": 240, "serve": 600, "kernels": 180,
                   "multichip": 700}


# ---------------------------------------------------------------------------
# parent: no jax, one child at a time
# ---------------------------------------------------------------------------

def main() -> int:
    t_start = time.time()
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    device = None
    try:
        for phase in ("train", "serve", "kernels", "multichip"):
            if phase == "multichip" and device["count"] < 4:
                print(f"multichip: not run, {device['count']} device(s)",
                      flush=True)
                continue
            result = _run_child(phase, tmp)
            if result is None:
                return 1
            device = device or result["device"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"chip_smoke: all phases passed in {time.time() - t_start:.0f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def _run_child(phase: str, tmp: str):
    """Run one phase in its own process; the result it wrote (the device
    it ran on), or None — after saying why on stderr — if it failed, hung
    or wrote none."""
    result_path = os.path.join(tmp, phase + ".json")
    code = (f"import chip_smoke; "
            f"chip_smoke.run_phase({phase!r}, {tmp!r})")
    print(f"=== {phase} ===", flush=True)
    t0 = time.time()
    proc = subprocess.Popen([sys.executable, "-c", code], cwd=HERE,
                            stdin=subprocess.DEVNULL)
    try:
        rc = proc.wait(timeout=PHASE_TIMEOUT_S[phase])
    except subprocess.TimeoutExpired:
        print(f"chip_smoke: phase {phase} still running after "
              f"{PHASE_TIMEOUT_S[phase]}s — killed", file=sys.stderr)
        return None
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        print(f"chip_smoke: phase {phase} failed (exit {rc})",
              file=sys.stderr)
        return None
    if not os.path.exists(result_path):
        print(f"chip_smoke: phase {phase} exited 0 without a result",
              file=sys.stderr)
        return None
    with open(result_path) as f:
        result = json.load(f)
    print(f"=== {phase} ok in {time.time() - t0:.0f}s ===", flush=True)
    return result


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def run_phase(phase: str, tmp: str) -> None:
    """Child entry: check the device, run the phase, write its result. Any
    exception propagates — the child exits non-zero and the parent stops."""
    device = _require_tpu()
    {"train": phase_train, "serve": phase_serve, "kernels": phase_kernels,
     "multichip": phase_multichip}[phase](tmp)
    with open(os.path.join(tmp, phase + ".json"), "w") as f:
        json.dump({"device": device}, f)


def _check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED — {what}")


def _require_tpu() -> dict:
    """Print what jax found and refuse anything but a TPU. Runs before any
    model is built; also where the compile cache is placed."""
    from importlib.metadata import PackageNotFoundError, version

    from paddle_tpu.framework.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    print(f"platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"compile_cache={cache_dir}", flush=True)
    _check(d0.platform == "tpu",
           f"platform is {d0.platform!r}, not 'tpu': this script only "
           f"passes on an accelerator")
    return device


def _mem_gb(dev, key: str = "peak_bytes_in_use") -> float:
    return dev.memory_stats()[key] / 1e9


def _count_kernels(lowered_text: str) -> int:
    return lowered_text.count("tpu_custom_call")


def _train(eng, ids, labels, what: str) -> None:
    """TRAIN_STEPS fused steps on one fixed batch; the loss checks of
    phases 1 and 5."""
    import jax
    import numpy as np

    vocab = eng.model.config.vocab_size
    losses, times = [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        loss = jax.block_until_ready(eng.step(ids, labels))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    print(f"{what}: losses {' '.join(f'{x:.4f}' for x in losses)}",
          flush=True)
    print(f"{what}: first step (with compile) {times[0]:.1f}s, later steps "
          f"{' '.join(f'{t * 1e3:.0f}' for t in times[1:])} ms", flush=True)
    _check(all(np.isfinite(losses)), f"{what}: non-finite loss {losses}")
    _check(abs(losses[0] - math.log(vocab)) < 0.5,
           f"{what}: first loss {losses[0]:.3f} is not within 0.5 of "
           f"ln({vocab}) = {math.log(vocab):.3f}")
    _check(losses[-1] < losses[0],
           f"{what}: loss did not fall on a fixed batch: {losses}")


def phase_train(tmp: str) -> None:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(SEED)
    cfg = LlamaConfig(**TRAIN_MODEL)
    model = LlamaForCausalLM(cfg)
    eng = Engine(model, mesh=None, lr=TRAIN_LR, clip_norm=1.0)
    ids = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (TRAIN_BATCH, TRAIN_SEQ)).astype(np.int32)
    _train(eng, ids, ids, "train")
    calls = _count_kernels(eng._jit_step.lower(
        eng.params, eng.m, eng.v, eng.step_count, ids, ids).as_text())
    print(f"train: {cfg.num_params() / 1e6:.0f}M params, batch "
          f"{TRAIN_BATCH} x seq {TRAIN_SEQ}, {calls} tpu_custom_call(s) in "
          f"the lowered step, peak device memory "
          f"{_mem_gb(jax.devices()[0]):.2f} GB in buffers + "
          f"{_mem_gb(jax.devices()[0], 'peak_bytes_reserved'):.2f} GB "
          f"reserved for the program's temporaries", flush=True)
    _check(calls >= TRAIN_KERNEL_CALLS,
           f"train: lowered step holds {calls} tpu_custom_call(s), expected "
           f">= {TRAIN_KERNEL_CALLS} — attention fell off the flash kernels")


def _wave():
    """The serving wave: 16 requests sharing a 48-token system prefix,
    max_new cycling 16/32/48/64, every fourth one seeded-sampled."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    vocab = SERVE_MODEL["vocab_size"]
    system = rng.integers(0, vocab, (WAVE_SHARED,)).astype(np.int32)
    wave = []
    for i in range(WAVE_REQUESTS):
        tail = rng.integers(0, vocab,
                            (WAVE_PROMPT - WAVE_SHARED,)).astype(np.int32)
        spec = dict(prompt_ids=np.concatenate([system, tail]),
                    max_new_tokens=(i % 4 + 1) * WAVE_MAX_NEW // 4)
        if i % 4 == 2:
            spec.update(temperature=0.8, top_p=0.9, top_k=40, seed=100 + i)
        wave.append(spec)
    return wave


def _serve_wave(eng, wave, what: str):
    """One wave through an engine; every request must finish, unfailed,
    with exactly the tokens it asked for. Returns the token streams."""
    from paddle_tpu.inference.serving import Request

    reqs = [Request(**spec) for spec in wave]
    for r in reqs:
        eng.add_request(r)
    eng.run_until_done()
    for i, (r, spec) in enumerate(zip(reqs, wave)):
        _check(r.done and not r.failed,
               f"{what}: request {i} done={r.done} failed={r.failed} "
               f"error={r.error}")
        _check(len(r.output) == spec["max_new_tokens"],
               f"{what}: request {i} returned {len(r.output)} tokens, asked "
               f"for {spec['max_new_tokens']}")
    return [[int(t) for t in r.output] for r in reqs]


def _identity(name: str, got, ref, rows) -> None:
    """Report (never assert) how many of ``rows`` carry identical streams,
    and where the others first part."""
    same, firsts = 0, []
    for i in rows:
        n = min(len(got[i]), len(ref[i]))
        diff = next((j for j in range(n) if got[i][j] != ref[i][j]), None)
        if diff is None:
            same += 1
        else:
            firsts.append((i, diff))
    where = ("" if not firsts else "; first divergence (request, position): "
             + " ".join(f"({i},{j})" for i, j in firsts))
    print(f"identity {name}: {same}/{len(rows)} greedy streams identical"
          f"{where}", flush=True)


def _decode_kernels(eng) -> int:
    """tpu_custom_call count of the engine's decode program, lowered from
    its live state the way ``_decode_block_inner`` dispatches it."""
    return _count_kernels(eng._jit_mega.lower(
        eng._params, eng._last_tok, eng.caches["kv"], eng.caches["tables"],
        eng._dev_pos, eng._dev_act, *eng._dev_samp, n_steps=eng.block_size,
        do_sample=False).as_text())


def _build_server():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(SEED)
    return LlamaForCausalLM(LlamaConfig(**SERVE_MODEL))


def _prefix_engine(model, **kw):
    from paddle_tpu.inference.serving import (ContinuousBatchingEngine,
                                              PrefixCacheConfig)

    return ContinuousBatchingEngine(
        model, max_batch=SERVE_SLOTS, max_len=SERVE_MAX_LEN,
        page_size=SERVE_PAGE, block_size=SERVE_BLOCK,
        prefix_cache=PrefixCacheConfig(extra_blocks=8), **kw)


def phase_serve(tmp: str) -> None:
    import jax
    import numpy as np

    from paddle_tpu.inference.serving import ContinuousBatchingEngine

    model = _build_server()
    wave = _wave()
    greedy = [i for i, s in enumerate(wave) if "temperature" not in s]

    # without a prefix cache: slot-owned pages and the bucketed prefill
    # program; with one: radix admission, packed prefill, first-token re-step
    plain = ContinuousBatchingEngine(
        model, max_batch=SERVE_SLOTS, max_len=SERVE_MAX_LEN,
        page_size=SERVE_PAGE, block_size=SERVE_BLOCK)
    streams = {}
    for name, eng in (("plain", plain), ("prefix", _prefix_engine(model))):
        t0 = time.perf_counter()
        first = _serve_wave(eng, wave, f"serve/{name} wave 1")
        t1 = time.perf_counter()
        hits = eng.stats.get("hit_tokens", 0)
        second = _serve_wave(eng, wave, f"serve/{name} wave 2")
        t2 = time.perf_counter()
        _check(first == second,
               f"serve/{name}: the second wave's streams differ from the "
               f"first's (requests "
               f"{[i for i in range(len(wave)) if first[i] != second[i]]})")
        calls = _decode_kernels(eng)
        print(f"serve/{name}: {len(wave)} requests x 2 waves ok "
              f"({sum(len(s) for s in first)} tokens each), wave 1 "
              f"{t1 - t0:.1f}s (with compile), wave 2 {t2 - t1:.2f}s, "
              f"{calls} tpu_custom_call(s) in the lowered decode program",
              flush=True)
        _check(calls == SERVE_KERNEL_CALLS,
               f"serve/{name}: lowered decode program holds {calls} "
               f"tpu_custom_call(s), expected {SERVE_KERNEL_CALLS} — decode "
               f"attention fell off the paged kernel")
        if name == "prefix":
            gained = eng.stats["hit_tokens"] - hits
            print(f"serve/prefix: prefix cache hit_tokens rose by {gained} "
                  f"on the second wave", flush=True)
            _check(gained > 0, "serve/prefix: hit_tokens did not rise on a "
                               "wave that repeats a 48-token prefix")
        streams[name] = first

    # phase 4 — reported, not asserted: these identities were only ever
    # pinned in float32 on the CPU
    _identity("prefix == plain", streams["prefix"], streams["plain"], greedy)
    ref = {}
    for lo in range(0, len(wave), SERVE_SLOTS):
        rows = range(lo, min(lo + SERVE_SLOTS, len(wave)))
        ids = np.stack([wave[i]["prompt_ids"] for i in rows])
        toks = np.asarray(model.generate(
            ids, max_new_tokens=WAVE_MAX_NEW, temperature=0.0,
            max_length=SERVE_MAX_LEN).numpy())
        for i, row in zip(rows, toks):
            ref[i] = [int(t) for t in row]
    for name in ("plain", "prefix"):
        _identity(f"{name} == generate()", streams[name], ref, greedy)
    print(f"serve: peak device memory {_mem_gb(jax.devices()[0]):.2f} GB",
          flush=True)
    # phase 5 compares its tp=4 streams with these
    with open(os.path.join(tmp, "prefix_streams.json"), "w") as f:
        json.dump(streams["prefix"], f)


#: Kernel-vs-reference bounds. Inputs are bf16, both sides accumulate in
#: float32 (the reference at "highest" matmul precision) and round the
#: result to bf16 once, so two correct results differ by a bf16 ulp
#: (2^-8 relative) where their float32 values straddle a rounding boundary:
#: ~2e-3 rms. A wrong mask, a dropped block or a stale DMA buffer moves
#: whole rows by O(1). 1e-2 rms / 5e-2 of the largest value sits between.
KERNEL_RMS_TOL, KERNEL_MAX_TOL = 1e-2, 5e-2


def _check_close(what: str, got, ref) -> None:
    """rms error / rms ref and max error / max |ref|, in float32, against
    the bounds above."""
    import jax.numpy as jnp

    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    err = got - ref
    rms = float(jnp.sqrt(jnp.mean(err * err) / jnp.mean(ref * ref)))
    mx = float(jnp.max(jnp.abs(err)) / jnp.max(jnp.abs(ref)))
    print(f"kernels/{what}: rel rms err {rms:.2e}, max err / max ref "
          f"{mx:.2e}", flush=True)
    _check(rms <= KERNEL_RMS_TOL and mx <= KERNEL_MAX_TOL,
           f"kernels/{what}: rel rms {rms:.3e} (bound {KERNEL_RMS_TOL}), "
           f"max {mx:.3e} (bound {KERNEL_MAX_TOL}) against the reference")


def phase_kernels(tmp: str) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.flash_attention import (_xla_reference,
                                                flash_attention)

    # flash attention at the trainer's shape: [b, s, h, d], GQA 16/4
    b, s, d = TRAIN_BATCH, TRAIN_SEQ, 128
    hq, hkv = (TRAIN_MODEL["num_attention_heads"],
               TRAIN_MODEL["num_key_value_heads"])
    kq, kk, kv, kw = jax.random.split(jax.random.key(SEED), 4)
    q = jax.random.normal(kq, (b, s, hq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.bfloat16)
    w = jax.random.normal(kw, (b, s, hq, d), jnp.bfloat16)
    scale = d ** -0.5

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
    ref = lambda q, k, v: _xla_reference(q, k, v, True, scale)
    fwd = jax.jit(flash)
    calls = _count_kernels(fwd.lower(q, k, v).as_text())
    _check(calls == 1, f"kernels/flash: forward lowered to {calls} "
                       f"tpu_custom_call(s), expected the one Pallas kernel")
    grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(ref)(q, k, v)
        ref_grads = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    _check_close("flash fwd", fwd(q, k, v), ref_out)
    for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        _check_close(f"flash {name}", g, rg)

    # paged decode at the server's shape (MHA 16/16, 8 pages a row), and at
    # the shape class of the benchmark's serving cell (GQA 16/8, 24 rows of
    # 128 pages, ragged to 2,048); one empty row in each
    heads = SERVE_MODEL["num_attention_heads"]
    _paged_decode_check("paged decode", heads, heads,
                        SERVE_MAX_LEN // SERVE_PAGE,
                        [1, 16, 17, 0, 64, 100, 127, 128])
    _paged_decode_check("paged decode gqa", 16, 8, 128, CELL_CONTEXTS)


#: 24 context lengths like the chat-batch cell's: 80 to 2,048 around a mean
#: of 700, one row empty
CELL_CONTEXTS = [593, 478, 348, 2048, 485, 837, 897, 80, 1828, 1540, 418, 0,
                 853, 411, 549, 997, 980, 349, 365, 345, 475, 502, 380, 764]
#: HBM bytes a second of one v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES_S = 819e9


def _paged_decode_check(what: str, hq: int, hkv: int, maxp: int,
                        contexts) -> None:
    """The paged decode kernel against its reference at "highest", head_dim
    128 and the server's page size; prints (and asserts nothing about) its
    time a call, chained in one program, and the share of the chip's
    bandwidth that the bytes the benchmark reckons for the call make of it
    (whole pages of context, q and o)."""
    import jax
    import jax.numpy as jnp

    from chipbench.ops.paged_decode import paged_decode_bytes
    from paddle_tpu.ops.paged_attention import (paged_decode_attention,
                                                paged_decode_reference)

    b, d, page = len(contexts), 128, SERVE_PAGE
    n_pages = b * maxp
    kq, kk, kv, kt = jax.random.split(jax.random.key(SEED + 1), 4)
    q = jax.random.normal(kq, (b, hq, d), jnp.bfloat16)
    kc = jax.random.normal(kk, (n_pages, hkv, page, d), jnp.bfloat16)
    vc = jax.random.normal(kv, (n_pages, hkv, page, d), jnp.bfloat16)
    tables = jax.random.permutation(kt, n_pages).reshape(
        b, maxp).astype(jnp.int32)
    lens = jnp.asarray(contexts, jnp.int32)
    paged = jax.jit(paged_decode_attention)
    calls = _count_kernels(paged.lower(q, kc, vc, tables, lens).as_text())
    _check(calls == 1, f"kernels/{what}: decode lowered to {calls} "
                       f"tpu_custom_call(s), expected the one Pallas kernel")
    got = paged(q, kc, vc, tables, lens)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_decode_reference)(q, kc, vc, tables, lens)
    _check_close(what, got, want)
    empty = contexts.index(0)
    _check(not bool(jnp.any(got[empty] != 0)),
           f"kernels/{what}: the zero-length row is not all zeros")

    n_calls = 200
    chained = jax.jit(lambda q: jax.lax.fori_loop(
        0, n_calls, lambda _, x: paged_decode_attention(
            x, kc, vc, tables, lens), q))
    jax.block_until_ready(chained(q))
    t0 = time.perf_counter()
    jax.block_until_ready(chained(q))
    us = (time.perf_counter() - t0) / n_calls * 1e6
    nbytes = paged_decode_bytes(contexts, hkv, hq, d, page)
    print(f"kernels/{what}: {us:.1f} us a call over {n_calls} chained calls, "
          f"{nbytes / 1e6:.1f} MB: {nbytes / V5E_HBM_BYTES_S / us * 1e8:.1f}% "
          f"of {V5E_HBM_BYTES_S / 1e9:.0f} GB/s", flush=True)


def _spread(what: str, arr) -> None:
    """A large array's shards sit on 4 distinct devices, and no device holds
    more than twice what another does."""
    import jax

    devs = jax.devices()[:4]
    owners = {s.device for s in arr.addressable_shards}
    used = [_mem_gb(d, "bytes_in_use") for d in devs]
    print(f"multichip/{what}: {tuple(arr.shape)} array on {len(owners)} "
          f"device(s); bytes_in_use per device "
          f"{' '.join(f'{u:.2f}' for u in used)} GB", flush=True)
    _check(owners == set(devs),
           f"multichip/{what}: shards sit on {len(owners)} device(s), not "
           f"the mesh's 4")
    _check(max(used) <= 2 * min(used),
           f"multichip/{what}: per-device bytes_in_use {used} GB differ by "
           f"more than 2x — the work is not spread")


def _multichip_train() -> None:
    """The trainer of phase 1 on fsdp2 x tp2, devices as make_mesh picks."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import (Engine, axis_rules,
                                                      make_mesh)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    mesh = make_mesh({"fsdp": 2, "tp": 2})
    paddle.seed(SEED)
    cfg = LlamaConfig(**TRAIN_MODEL)
    with axis_rules(mesh):
        model = LlamaForCausalLM(cfg)
    eng = Engine(model, mesh, lr=TRAIN_LR, clip_norm=1.0)
    ids = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (TRAIN_BATCH, TRAIN_SEQ)).astype(np.int32)
    ids_d, labels_d = eng.shard_batch(ids, ids)
    _train(eng, ids_d, labels_d, "multichip/train")
    _spread("train", max(eng.params, key=lambda a: a.size))
    peaks = [_mem_gb(d) for d in jax.devices()[:4]]
    print(f"multichip/train: peak device memory "
          f"{' '.join(f'{p:.2f}' for p in peaks)} GB", flush=True)


def _multichip_serve(tmp: str) -> None:
    """The prefix-cache server of phase 2 on tp=4, devices as MeshConfig picks;
    streams compared (reported, not asserted) with phase 2's tp=1 ones."""
    from paddle_tpu.inference.serving import MeshConfig

    eng = _prefix_engine(_build_server(), mesh=MeshConfig(tp=4))
    wave = _wave()
    streams = _serve_wave(eng, wave, "multichip/serve")
    with open(os.path.join(tmp, "prefix_streams.json")) as f:
        single = json.load(f)
    _spread("serve", max(eng._params, key=lambda a: a.size))
    _identity("tp=4 == tp=1", streams, single,
              [i for i, s in enumerate(wave) if "temperature" not in s])


def phase_multichip(tmp: str) -> None:
    _multichip_train()
    # the trainer's arrays died with its frame; the server starts clean
    _multichip_serve(tmp)


if __name__ == "__main__":
    sys.exit(main())
