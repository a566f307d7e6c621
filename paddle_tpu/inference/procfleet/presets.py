"""Picklable engine factories for process-replica workers.

A :class:`~paddle_tpu.inference.procfleet.worker.WorkerSpec` must name a
factory the SPAWNED process can import and call — a module-level function,
referenced by pickling or by ``"module:qualname"`` string. Test/drill/bench
factories live here (an importable module, not a test file or ``__main__``)
so every harness spawns workers through one audited path.

Determinism contract: a factory SEEDS the global rng before building its
model, so N worker processes build bit-identical weights — the same
property the in-process fleet gets from sharing one model object, and the
foundation of the byte-identical-failover guarantee across processes.
"""

from __future__ import annotations

__all__ = ["tiny_llama_engine", "tiny_llama_mesh_engine",
           "tiny_llama_prefix_engine"]


def tiny_llama_engine(seed: int = 13, num_hidden_layers: int = 1,
                      max_batch: int = 2, max_len: int = 32,
                      page_size: int = 8, block_size: int = 2,
                      max_queue=None, prefix_cache: bool = False, **kw):
    """CPU-sized 1-layer Llama serving engine, deterministically seeded —
    the worker-side twin of the engines tests/test_fleet.py builds."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    cfg = LlamaConfig.tiny(num_hidden_layers=num_hidden_layers)
    model = LlamaForCausalLM(cfg)
    return ContinuousBatchingEngine(
        model, max_batch=max_batch, max_len=max_len, page_size=page_size,
        block_size=block_size, max_queue=max_queue,
        prefix_cache=prefix_cache, **kw)


def tiny_llama_prefix_engine(**kw):
    """The prefix-cache variant (KV-chain migration needs dynamic block
    tables on both tiers — inference/disagg.py)."""
    kw.setdefault("prefix_cache", True)
    return tiny_llama_engine(**kw)


def tiny_llama_mesh_engine(**kw):
    """Prefix-cache variant for mesh-sharded workers: sharded serving
    requires a prefix cache, and the worker injects
    ``mesh=MeshConfig(tp, devices=<its group>)`` on top of these kwargs
    (``WorkerSpec.mesh`` — docs/SERVING.md "Sharded serving")."""
    kw.setdefault("prefix_cache", True)
    kw.setdefault("max_batch", 4)
    return tiny_llama_engine(**kw)
