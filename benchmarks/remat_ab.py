"""In-process interleaved A/B of remat operating points on the north-star
llama shape (853M, seq 4096, GQA 16/4).

Variants (one shared param set — pure fwd+bwd, no optimizer state, so all
variants fit HBM together and interleave honestly):
  - noremat      : recompute=False              (the headline regime)
  - remat_flash  : recompute=True, policy saves flash out+lse (round-4 state)
  - remat_qkv    : recompute=True, policy additionally saves rope'd q/k/v
                   (kills the qkv-proj + rope + norm1 recompute)

Each timed sample is a jitted lax.scan chain over `ITERS` fresh batches whose
carry folds the loss AND one element of every grad (so no dW matmul can be
DCE'd); one device_get fences the chain — no per-step dispatch floor in the
numbers. Rounds are interleaved across variants so chip-state drift hits all
sides equally; report best-of-N per variant.

Usage: python benchmarks/remat_ab.py [batch] [rounds]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.jit.api import _collect_state, _Swap
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 4
ROUNDS = int(sys.argv[2]) if len(sys.argv) > 2 else 3
SEQ = 4096
ITERS = 4


def main():
    dev = jax.devices()[0]
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=16, num_attention_heads=16, num_key_value_heads=4,
        max_position_embeddings=4096, dtype="bfloat16", recompute=True)
    model = LlamaForCausalLM(cfg)
    _, tensors = _collect_state(model)
    params = [t._data for t in tensors]
    n_params = cfg.num_params()

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (ITERS, BATCH, SEQ)),
                      jnp.int32)

    def make_step(recompute, policy):
        def step(ps, batch_ids):
            def loss_of(ps_):
                with _Swap(tensors, ps_):
                    return model.loss_fn(batch_ids, batch_ids)

            l, g = jax.value_and_grad(loss_of)(ps)
            # keep every dW live (one element each — a DCE'd backward matmul
            # would otherwise make remat look free); params must flow in as
            # ARGUMENTS (closing over them would bake 1.7GB of literals into
            # the HLO and stall the compiler)
            probe = sum(gg.ravel()[0].astype(jnp.float32) for gg in g)
            ps = [p_ + 0.0 * gg.astype(p_.dtype) for p_, gg in zip(ps, g)]
            return ps, l.astype(jnp.float32) + 0.0 * probe

        def chain(ps, ids_stack):
            # trace-time switch: config mutated before each variant's first
            # call, read inside the traced model
            cfg.recompute = recompute
            cfg.remat_policy = policy
            _, losses = jax.lax.scan(step, list(ps), ids_stack)
            return losses.sum()

        return jax.jit(chain)

    variants = {
        "noremat": make_step(False, "flash"),
        "remat_flash": make_step(True, "flash"),
        "remat_qkv": make_step(True, "flash_qkv"),
    }

    peak = 197e12 if "v5 lite" in dev.device_kind.lower() else 459e12
    flops_per_token = 6.0 * n_params + 6.0 * 16 * 2048 * SEQ

    # compile + one warm pass each (mutating cfg between traces is safe: the
    # policy is baked in at trace time)
    best = {}
    for name, fn in variants.items():
        try:
            t0 = time.perf_counter()
            jax.device_get(fn(params, ids))
            print(f"# {name}: compiled+warm in {time.perf_counter()-t0:.1f}s",
                  flush=True)
            best[name] = float("inf")
        except Exception as e:
            print(f"# {name}: FAILED {e!r}", flush=True)

    for r in range(ROUNDS):
        for name, fn in variants.items():
            if name not in best:
                continue
            t0 = time.perf_counter()
            jax.device_get(fn(params, ids))
            dt = (time.perf_counter() - t0) / ITERS
            best[name] = min(best[name], dt)
            tok = BATCH * SEQ / dt
            print(f"round {r} {name:12s}: {dt*1e3:7.1f} ms/step  "
                  f"{tok:9.0f} tok/s  mfu {tok*flops_per_token/peak:.3f}",
                  flush=True)

    print("\n== best-of-%d (fwd+bwd only, batch %d) ==" % (ROUNDS, BATCH))
    for name, dt in best.items():
        tok = BATCH * SEQ / dt
        print(f"{name:12s}: {dt*1e3:7.1f} ms/step  {tok:9.0f} tok/s  "
              f"mfu {tok*flops_per_token/peak:.3f}")
    if "noremat" in best and "remat_qkv" in best:
        print(f"remat_qkv tax vs noremat: "
              f"{(best['remat_qkv']/best['noremat']-1)*100:.1f}%")
    if "noremat" in best and "remat_flash" in best:
        print(f"remat_flash tax vs noremat: "
              f"{(best['remat_flash']/best['noremat']-1)*100:.1f}%")


if __name__ == "__main__":
    main()
