"""Llama family — the flagship pretraining model.

Parity anchor: the reference trains this architecture in its hybrid-strategy tests
(/root/reference/test/auto_parallel/hybrid_strategy/semi_auto_llama.py:33 — hidden
4096, GQA, RoPE, RMSNorm, SwiGLU) using ColumnParallelLinear/RowParallelLinear
(fleet/layers/mpu/mp_layers.py:334,541) + flash attention
(nn/functional/flash_attention.py:195).

TPU-native design: one set of plain Layers whose parameters carry *logical axis*
names; sharding (tp / fsdp / sep / dp) is applied by rules at the mesh boundary
(distributed/auto_parallel/logical_sharding.py) and GSPMD inserts the collectives.
The same model class is therefore the single-chip model, the TP model, and the
FSDP model — no per-strategy layer forks like the reference's mpu vs plain nn.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...distributed.auto_parallel.logical_sharding import annotate, constrain, current_mesh
from ...distributed.auto_parallel.serving_sharding import gather_output_shards
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer.layers import Layer, LayerList
from ..generation_utils import GenerationMixin, causal_cache_bias


def _scope(name):
    """Decorator: the method's ops carry ``name`` in their name stack (device
    traces and lowered text; docs/OBSERVABILITY.md "Program spans and device
    names"). Metadata only."""
    return functools.partial(jax.named_call, name=name)


class LlamaConfig:
    def __init__(
        self,
        vocab_size: int = 32000,
        hidden_size: int = 4096,
        intermediate_size: int = 11008,
        num_hidden_layers: int = 32,
        num_attention_heads: int = 32,
        num_key_value_heads: Optional[int] = None,
        max_position_embeddings: int = 4096,
        rms_norm_eps: float = 1e-6,
        rope_theta: float = 10000.0,
        initializer_range: float = 0.02,
        tie_word_embeddings: bool = False,
        dtype: str = "float32",
        recompute: bool = False,
        remat_policy: str = "flash",
        remat_every: int = 1,
        use_flash_attention: bool = True,
        sequence_parallel: bool = False,
        num_experts: int = 1,
        moe_topk: int = 2,
        moe_dispatch: str = "auto",
        moe_gate: str = "gshard",
        moe_aux_weight: float = 0.01,
        moe_capacity_factor: float = 1.25,
        fused_ce: bool = True,
        fused_ce_chunk: int = 1024,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        self.tie_word_embeddings = tie_word_embeddings
        self.dtype = dtype
        self.recompute = recompute
        if remat_policy not in ("flash", "flash_qkv", "flash_mlp", "full"):
            raise ValueError(f"remat_policy must be 'flash', 'flash_qkv', "
                             f"'flash_mlp' or 'full', got {remat_policy!r}")
        self.remat_policy = remat_policy
        # partial remat: layer i is rematerialized iff i % remat_every == 0
        # (1 = every layer, the reference recompute default; 2 = half the
        # stack — trades activation memory back for the recompute FLOPs,
        # the measured ~13% remat tax on the north-star shape)
        if remat_every < 1:
            raise ValueError(f"remat_every must be >= 1 (got {remat_every}); "
                             "use recompute=False to disable remat")
        self.remat_every = remat_every
        self.use_flash_attention = use_flash_attention
        self.sequence_parallel = sequence_parallel
        self.num_experts = num_experts
        self.moe_topk = moe_topk
        self.moe_dispatch = moe_dispatch
        self.moe_gate = moe_gate
        self.moe_aux_weight = moe_aux_weight
        self.moe_capacity_factor = moe_capacity_factor
        # chunked lm-head+CE (ops/fused_ce.py) — skips the [b, s, V] logits
        # materialization in the training loss; generation is unaffected
        self.fused_ce = fused_ce
        self.fused_ce_chunk = fused_ce_chunk

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def num_params(self) -> int:
        """Parameter count (for MFU math)."""
        h, v, m = self.hidden_size, self.vocab_size, self.intermediate_size
        kvh = self.num_key_value_heads * self.head_dim
        per_layer = (
            h * h + 2 * h * kvh + h * h  # q, k, v, o
            + 3 * h * m                   # gate, up, down
            + 2 * h                       # two rmsnorms
        )
        total = v * h + self.num_hidden_layers * per_layer + h
        if not self.tie_word_embeddings:
            total += h * v
        return total

    @classmethod
    def tiny(cls, **over):
        """Small config for tests / multichip dry-runs. Dims divide tp/fsdp/sep=2."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                 max_position_embeddings=128)
        d.update(over)
        return cls(**d)


def _rope_cos_sin(seq_len: int, head_dim: int, theta: float, dtype):
    """Rotary tables [seq, head_dim] (half-rotated layout, GPT-NeoX style — matches
    reference fused_rotary_position_embedding use_neox_rotary_style=True)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)                       # [s, d/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)       # [s, d]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rotary_pos_emb(q, k, cos, sin):
    """q,k: [b, s, h, d]; cos/sin: [s, d] (shared positions) or [b, s, d]
    (per-row positions, e.g. left-padded decode) — broadcast over heads."""
    if cos.ndim == 3:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    return q * cos + _rotate_half(q) * sin, k * cos + _rotate_half(k) * sin


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        hd = config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        init = I.Normal(std=config.initializer_range)
        mk = lambda din, dout: self.create_parameter(
            [din, dout], dtype=config.dtype, default_initializer=init)
        self.q_proj_weight = annotate(mk(h, self.num_heads * hd), "embed", "heads")
        self.k_proj_weight = annotate(mk(h, self.num_kv_heads * hd), "embed", "heads")
        self.v_proj_weight = annotate(mk(h, self.num_kv_heads * hd), "embed", "heads")
        self.o_proj_weight = annotate(mk(self.num_heads * hd, h), "heads", "embed")

    @_scope("pt.attn")
    def forward(self, hidden, cos, sin, attn_bias=None):
        b, s, h = hidden.shape if isinstance(hidden, Tensor) else hidden.shape
        hd = self.config.head_dim
        x = hidden._data if isinstance(hidden, Tensor) else hidden
        q = jnp.matmul(x, self.q_proj_weight._data).reshape(b, s, self.num_heads, hd)
        k = jnp.matmul(x, self.k_proj_weight._data).reshape(b, s, self.num_kv_heads, hd)
        v = jnp.matmul(x, self.v_proj_weight._data).reshape(b, s, self.num_kv_heads, hd)
        q = constrain(q, "batch", "seq", "heads", "head_dim")
        k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
        v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        # named for the 'flash_qkv' remat policy: saving the rope'd q/k/v
        # (~100MB/layer at the 853M b4 seq-4096 shape) lets backward skip the
        # qkv-projection + rope + input-norm recompute entirely
        from jax.ad_checkpoint import checkpoint_name

        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        out = _attention(q, k, v, self.config, attn_bias)
        out = out.reshape(b, s, self.num_heads * hd)
        out = jnp.matmul(out, self.o_proj_weight._data)
        out = constrain(out, "batch", "seq", "embed")
        return out

    @_scope("pt.attn")
    def decode_step(self, x, cos, sin, k_cache, v_cache, pos, pad_bias=None):
        """KV-cache attention for generation (used for prefill AND decode).

        x: [b, s, h] chunk occupying absolute positions [pos, pos+s);
        caches: [b, max_len, kv_heads, hd]; cos/sin sliced for the chunk's
        positions ([s, d] shared or [b, s, d] per-row when left-padded).
        ``pad_bias`` [b, 1, 1, max_len] masks pad cache columns.
        Returns (out, k_cache, v_cache).
        """
        x = x._data if isinstance(x, Tensor) else x
        b, s, _ = x.shape
        hd = self.config.head_dim
        q = jnp.matmul(x, self.q_proj_weight._data).reshape(b, s, self.num_heads, hd)
        k = jnp.matmul(x, self.k_proj_weight._data).reshape(b, s, self.num_kv_heads, hd)
        v = jnp.matmul(x, self.v_proj_weight._data).reshape(b, s, self.num_kv_heads, hd)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                               (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                               (0, pos, 0, 0))
        bias = causal_cache_bias(k_cache, pos, s, pad_bias)
        from ...nn.functional.flash_attention import _xla_attention

        out = _xla_attention(q, k_cache, v_cache, bias=bias, causal=False)
        out = out.reshape(b, s, self.num_heads * hd)
        return jnp.matmul(out, self.o_proj_weight._data), k_cache, v_cache

    @_scope("pt.attn")
    def paged_decode_step(self, x, cos, sin, k_pages, v_pages, tables, pos):
        """Paged-KV generation step (serving suite, ops/paged_attention.py).

        Pools [num_pages, kv_heads, page, hd]; tables [b, pages_per_seq].
        Prefill chunks (s > 1, pos == 0) run causal flash over the chunk;
        decode steps (s == 1) run the paged decode kernel over the whole
        cache. K/V always scatter into the pages. Returns (out, k_pages,
        v_pages)."""
        from ...ops.flash_attention import flash_attention
        from ...ops.paged_attention import append_paged_kv, paged_decode_attention

        x = x._data if isinstance(x, Tensor) else x
        b, s, _ = x.shape
        hd = self.config.head_dim
        q = jnp.matmul(x, self.q_proj_weight._data).reshape(b, s, self.num_heads, hd)
        k = jnp.matmul(x, self.k_proj_weight._data).reshape(b, s, self.num_kv_heads, hd)
        v = jnp.matmul(x, self.v_proj_weight._data).reshape(b, s, self.num_kv_heads, hd)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        seq_ids = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
        positions = jnp.tile(pos + jnp.arange(s, dtype=jnp.int32), b)
        k_pages, v_pages = append_paged_kv(
            k_pages, v_pages, k.reshape(b * s, self.num_kv_heads, hd),
            v.reshape(b * s, self.num_kv_heads, hd), tables, positions, seq_ids)
        if s == 1:
            ctx = jnp.full((b,), pos + 1, jnp.int32)
            out = paged_decode_attention(q[:, 0], k_pages, v_pages, tables,
                                         ctx)[:, None]
        else:
            out = flash_attention(q, k, v, causal=True)
        out = out.reshape(b, s, self.num_heads * hd)
        return jnp.matmul(out, self.o_proj_weight._data), k_pages, v_pages

    @_scope("pt.attn")
    def paged_prefill_chunk(self, x, cos, sin, k_pages, v_pages, tables,
                            starts, page_aligned=False):
        """Prefill CHUNK at PER-ROW absolute offsets over cached history
        (prefix-cache / chunked-prefill serving path). x: [b, s, h] — row b
        holds tokens at absolute positions [starts[b], starts[b]+s);
        cos/sin [b, s, d] gathered per row. The chunk's k/v scatter into the
        pages first, then attention reads each row's own pages up to its
        last query under an absolute-position causal mask, in key blocks
        aligned to absolute positions — see paged_prefill_attention for the
        bit-identity-across-chunkings argument. ``page_aligned`` (static):
        every ``starts[b]`` is a multiple of the page, so the append may
        write whole pages (``ops.append_paged_chunk``); the packed prefill
        says so, the speculative verify window, which runs this body at any
        position, does not.

        Head counts come off the weight/pool shapes (not config), so the
        same body serves a tp shard inside the engine's serving shard_map
        (LOCAL heads + local kv pages per device — all math head-local);
        the attention output is all-gathered before the replicated o_proj
        (serving_sharding.py's column-parallel identity discipline)."""
        from ...ops.paged_attention import (append_paged_chunk,
                                            paged_prefill_attention)

        x = x._data if isinstance(x, Tensor) else x
        b, s, _ = x.shape
        hd = self.config.head_dim
        q = jnp.matmul(x, self.q_proj_weight._data).reshape(b, s, -1, hd)
        k = jnp.matmul(x, self.k_proj_weight._data).reshape(b, s, -1, hd)
        v = jnp.matmul(x, self.v_proj_weight._data).reshape(b, s, -1, hd)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        k_pages, v_pages = append_paged_chunk(
            k_pages, v_pages, k, v, tables, starts, page_aligned)
        out = paged_prefill_attention(q, k_pages, v_pages, tables, starts)
        out = gather_output_shards(out.reshape(b, s, -1))
        return jnp.matmul(out, self.o_proj_weight._data), k_pages, v_pages

    @_scope("pt.attn")
    def paged_token_step(self, x, cos, sin, k_pages, v_pages, tables, pos_vec):
        """ONE token per row at PER-ROW positions (continuous batching:
        every slot is at a different decode offset). x: [b, 1, h];
        cos/sin [b, 1, d] gathered per row; pos_vec [b] int32. Head counts
        come off the weight shapes so a tp shard (local heads, local kv
        pages) runs the same body; see paged_prefill_chunk."""
        from ...ops.paged_attention import append_paged_kv, paged_decode_attention

        x = x._data if isinstance(x, Tensor) else x
        b = x.shape[0]
        hd = self.config.head_dim
        q = jnp.matmul(x, self.q_proj_weight._data).reshape(b, 1, -1, hd)
        k = jnp.matmul(x, self.k_proj_weight._data).reshape(b, 1, -1, hd)
        v = jnp.matmul(x, self.v_proj_weight._data).reshape(b, 1, -1, hd)
        q, k = apply_rotary_pos_emb(q, k, cos, sin)
        k_pages, v_pages = append_paged_kv(
            k_pages, v_pages, k[:, 0], v[:, 0], tables, pos_vec)
        out = paged_decode_attention(q[:, 0], k_pages, v_pages, tables,
                                     pos_vec + 1)
        out = gather_output_shards(out.reshape(b, 1, -1))
        return jnp.matmul(out, self.o_proj_weight._data), k_pages, v_pages


def _attention(q, k, v, config, attn_bias=None):
    """Causal attention on raw arrays; routes to the Pallas kernel on TPU.

    Routing under a mesh:
      - no mesh / 1-device mesh → direct Pallas flash attention
      - sep (context-parallel) axis sharded → ring attention (ppermute over ICI)
      - dp/fsdp/tp sharded, seq whole → shard_map over (batch, heads), Pallas
        flash attention per shard (batched GQA kept in the index_map)
    """
    if config.use_flash_attention and attn_bias is None:
        from ...ops.flash_attention import flash_attention as fa
        from ...distributed.auto_parallel.pipeline import in_manual_pipeline

        mesh = current_mesh()
        if in_manual_pipeline():
            # inside shard_map(pp): no nested manual meshes — plain attention,
            # GSPMD still shards batch/heads over the auto axes
            from ...nn.functional.flash_attention import _xla_attention

            return _xla_attention(q, k, v, bias=attn_bias, causal=True)
        if mesh is None or mesh.size == 1:
            return fa(q, k, v, causal=True)
        sep = mesh.shape.get("sep", 1)
        if sep > 1:
            from ...ops.ring_attention import ring_attention

            return ring_attention(q, k, v, mesh, axis_name="sep", causal=True)
        from ...distributed.auto_parallel.logical_sharding import logical_to_spec

        tp = mesh.shape.get("tp", 1)
        dbatch = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        if q.shape[0] % dbatch == 0 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0:
            qspec = logical_to_spec(("batch", None, "heads", None), mesh)
            kspec = logical_to_spec(("batch", None, "kv_heads", None), mesh)
            f = jax.shard_map(
                lambda a, b, c: fa(a, b, c, causal=True),
                mesh=mesh,
                in_specs=(qspec, kspec, kspec),
                out_specs=qspec,
                check_vma=False,
            )
            return f(q, k, v)
    from ...nn.functional.flash_attention import _xla_attention

    return _xla_attention(q, k, v, bias=attn_bias, causal=True)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        init = I.Normal(std=config.initializer_range)
        mk = lambda din, dout: self.create_parameter(
            [din, dout], dtype=config.dtype, default_initializer=init)
        self.gate_proj_weight = annotate(mk(h, m), "embed", "mlp")
        self.up_proj_weight = annotate(mk(h, m), "embed", "mlp")
        self.down_proj_weight = annotate(mk(m, h), "mlp", "embed")

    @_scope("pt.mlp")
    def forward(self, x):
        from jax.ad_checkpoint import checkpoint_name

        x = x._data if isinstance(x, Tensor) else x
        g = jnp.matmul(x, self.gate_proj_weight._data)
        u = jnp.matmul(x, self.up_proj_weight._data)
        act = jax.nn.silu(g) * u   # swiglu — XLA fuses this into the matmuls
        act = constrain(act, "batch", "seq", "mlp")
        # named for the 'flash_mlp' remat policy (saveable, not saved by default)
        act = checkpoint_name(act, "mlp_act")
        # serving tp shard: gate/up are column-sharded, so the activation is
        # mlp-sharded — gather it whole before the replicated down_proj
        # (no-op outside a serving shard_map; see serving_sharding.py)
        act = gather_output_shards(act)
        out = jnp.matmul(act, self.down_proj_weight._data)
        return constrain(out, "batch", "seq", "embed")


class LlamaRMSNorm(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.eps = config.rms_norm_eps
        self.weight = annotate(
            self.create_parameter([config.hidden_size], dtype=config.dtype,
                                  default_initializer=I.Constant(1.0)),
            "norm")

    def forward(self, x):
        x = x._data if isinstance(x, Tensor) else x
        dt = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = (xf * jax.lax.rsqrt(var + self.eps)).astype(dt)
        return out * self.weight._data


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.input_layernorm = LlamaRMSNorm(config)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)
        if config.num_experts > 1:
            # Mixtral-class MoE FFN: swiglu experts over the ep mesh axis
            from ...incubate.distributed.models.moe import MoELayer, SwiGLUExpertFFN

            self.mlp = MoELayer(
                config.hidden_size, config.num_experts,
                experts=SwiGLUExpertFFN(config.num_experts, config.hidden_size,
                                        config.intermediate_size,
                                        dtype=config.dtype,
                                        initializer_range=config.initializer_range),
                gate=config.moe_gate, top_k=config.moe_topk,
                capacity_factor=config.moe_capacity_factor,
                dispatch_mode=getattr(config, "moe_dispatch", "auto"))
        else:
            self.mlp = LlamaMLP(config)

    def forward(self, hidden, cos, sin, attn_bias=None):
        x = hidden._data if isinstance(hidden, Tensor) else hidden
        x = x + self.self_attn(self.input_layernorm(x), cos, sin, attn_bias)
        y = self.mlp(self.post_attention_layernorm(x))
        x = x + (y._data if isinstance(y, Tensor) else y)
        if self.config.sequence_parallel:
            # Megatron-SP: the residual stream (and the norms computed from
            # it) lives sequence-sharded over sep AND tp between blocks;
            # GSPMD all-gathers into the projections and reduce-scatters out
            # of them (reference ColumnSequenceParallelLinear:427 semantics)
            return constrain(x, "batch", "seq_sp", "embed")
        return constrain(x, "batch", "seq", "embed")

    def decode_step(self, hidden, cos, sin, k_cache, v_cache, pos,
                    pad_bias=None):
        x = hidden._data if isinstance(hidden, Tensor) else hidden
        a, k_cache, v_cache = self.self_attn.decode_step(
            self.input_layernorm(x), cos, sin, k_cache, v_cache, pos,
            pad_bias=pad_bias)
        x = x + a
        y = self.mlp(self.post_attention_layernorm(x))
        x = x + (y._data if isinstance(y, Tensor) else y)
        return x, k_cache, v_cache

    def paged_decode_step(self, hidden, cos, sin, k_pages, v_pages, tables, pos):
        x = hidden._data if isinstance(hidden, Tensor) else hidden
        a, k_pages, v_pages = self.self_attn.paged_decode_step(
            self.input_layernorm(x), cos, sin, k_pages, v_pages, tables, pos)
        x = x + a
        y = self.mlp(self.post_attention_layernorm(x))
        x = x + (y._data if isinstance(y, Tensor) else y)
        return x, k_pages, v_pages

    def paged_token_step(self, hidden, cos, sin, k_pages, v_pages, tables,
                         pos_vec):
        x = hidden._data if isinstance(hidden, Tensor) else hidden
        a, k_pages, v_pages = self.self_attn.paged_token_step(
            self.input_layernorm(x), cos, sin, k_pages, v_pages, tables,
            pos_vec)
        x = x + a
        y = self.mlp(self.post_attention_layernorm(x))
        x = x + (y._data if isinstance(y, Tensor) else y)
        return x, k_pages, v_pages

    def paged_prefill_chunk(self, hidden, cos, sin, k_pages, v_pages, tables,
                            starts, page_aligned=False):
        x = hidden._data if isinstance(hidden, Tensor) else hidden
        a, k_pages, v_pages = self.self_attn.paged_prefill_chunk(
            self.input_layernorm(x), cos, sin, k_pages, v_pages, tables,
            starts, page_aligned)
        x = x + a
        y = self.mlp(self.post_attention_layernorm(x))
        x = x + (y._data if isinstance(y, Tensor) else y)
        return x, k_pages, v_pages


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = I.Normal(std=config.initializer_range)
        self.embed_tokens_weight = annotate(
            self.create_parameter([config.vocab_size, config.hidden_size],
                                  dtype=config.dtype, default_initializer=init),
            "vocab_in", "embed")
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config)

    def embed_and_rope(self, input_ids):
        """Token embedding + rope tables (shared by the plain and pp paths)."""
        ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        cfg = self.config
        # FSDP-style: all-gather the (embed-sharded) table before the lookup so
        # the gather is local — otherwise GSPMD falls back to full remat.
        table = constrain(self.embed_tokens_weight._data, None, None)
        x = jnp.take(table, ids, axis=0)
        x = constrain(x, "batch", "seq", "embed")
        cos, sin = _rope_cos_sin(ids.shape[1], cfg.head_dim, cfg.rope_theta, x.dtype)
        return x, cos, sin

    def forward(self, input_ids, attn_bias=None):
        cfg = self.config
        x, cos, sin = self.embed_and_rope(input_ids)
        remat = cfg.recompute and isinstance(x, jax.core.Tracer)
        moe = cfg.num_experts > 1
        aux_total = jnp.zeros((), jnp.float32) if moe else 0.0
        every = max(1, getattr(cfg, "remat_every", 1))
        for li, layer in enumerate(self.layers):
            if remat and li % every == 0:
                # closure holds the params (inputs, not recomputed); activations
                # inside the layer are rematerialized in backward — the TPU
                # analogue of fleet/recompute/recompute.py:455. The MoE aux loss
                # must be a checkpoint OUTPUT (reading the gate's side channel
                # outside the remat region would leak a tracer).
                def blk(h, c, s, lyr=layer):
                    y = lyr(h, c, s, attn_bias)
                    a = (_raw(lyr.mlp.get_loss()) if moe
                         else jnp.zeros((), jnp.float32))
                    return y, a

                x, aux = _remat(blk, cfg)(x, cos, sin)
            else:
                x = layer(x, cos, sin, attn_bias)
                aux = _raw(layer.mlp.get_loss()) if moe else 0.0
            if moe:
                aux_total = aux_total + aux
        self._moe_aux = aux_total
        return self.norm(x)


def remat_policy_of(cfg):
    """The jax.checkpoint policy for cfg.remat_policy: 'flash' SAVES the
    attention kernel's out+lse residuals (named in
    ops/flash_attention._flash_fwd) so backward skips re-running the flash
    forward kernel (verified: grad jaxpr drops from 4 to 3 pallas calls);
    'full' (None) recomputes everything."""
    p = getattr(cfg, "remat_policy", "flash")
    if p == "flash":
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")
    if p == "flash_qkv":
        # additionally saves the rope'd q/k/v heads — kills the qkv-proj +
        # rope + input-norm recompute for ~100MB/layer (853M b4 seq-4096);
        # the remat tax then reduces to o-proj + MLP recompute
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse", "attn_q", "attn_k", "attn_v")
    if p == "flash_mlp":
        # additionally saves the swiglu product — measured OOM on the 853M
        # seq-4096 batch-4 config (16.8G > 15.75G hbm); viable for smaller
        # models/batches only
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse", "mlp_act")
    return None


def _remat(fn, cfg):
    return jax.checkpoint(fn, policy=remat_policy_of(cfg))


def _decode_model(model: "LlamaModel", ids, caches, pos, pad_bias=None,
                  rope_offset=None):
    """Run a chunk through all layers with KV caches. ids: [b, s] at absolute
    positions [pos, pos+s); caches: list of (k, v) per layer.

    ``pad_bias``: [b, 1, 1, max_len] additive bias masking left-pad cache
    columns; ``rope_offset``: [b] per-row position shift (left padding moves
    each row's position 0 to its first real token)."""
    cfg = model.config
    table = model.embed_tokens_weight._data
    x = jnp.take(table, ids, axis=0)
    max_len = caches[0][0].shape[1]
    cos_full, sin_full = _rope_cos_sin(max_len, cfg.head_dim, cfg.rope_theta,
                                       x.dtype)
    s = ids.shape[1]
    if rope_offset is None:
        cos = jax.lax.dynamic_slice_in_dim(cos_full, pos, s, 0)
        sin = jax.lax.dynamic_slice_in_dim(sin_full, pos, s, 0)
    else:
        # per-row positions: [b, s] gather -> [b, s, d], clipped at 0 for pads
        positions = jnp.clip(pos + jnp.arange(s)[None, :]
                             - rope_offset[:, None], 0, max_len - 1)
        cos = cos_full[positions]
        sin = sin_full[positions]
    new_caches = []
    for layer, (kc, vc) in zip(model.layers, caches):
        x, kc, vc = layer.decode_step(x, cos, sin, kc, vc, pos,
                                      pad_bias=pad_bias)
        new_caches.append((kc, vc))
    return model.norm(x), new_caches


def _decode_model_paged(model: "LlamaModel", ids, caches, pos):
    """Paged-KV chunk decode: caches = {"kv": [(k_pages, v_pages)] per layer,
    "tables": [b, pages_per_seq]}. Left padding is not supported on this path
    (generate() rejects attention_mask with cache_impl='paged')."""
    cfg = model.config
    x = jnp.take(model.embed_tokens_weight._data, ids, axis=0)
    tables = caches["tables"]
    page = caches["kv"][0][0].shape[2]
    max_len = tables.shape[1] * page
    cos_full, sin_full = _rope_cos_sin(max_len, cfg.head_dim, cfg.rope_theta,
                                       x.dtype)
    s = ids.shape[1]
    cos = jax.lax.dynamic_slice_in_dim(cos_full, pos, s, 0)
    sin = jax.lax.dynamic_slice_in_dim(sin_full, pos, s, 0)
    new_kv = []
    for layer, (kp, vp) in zip(model.layers, caches["kv"]):
        x, kp, vp = layer.paged_decode_step(x, cos, sin, kp, vp, tables, pos)
        new_kv.append((kp, vp))
    return model.norm(x), {"kv": new_kv, "tables": tables}


class LlamaForCausalLM(GenerationMixin, Layer):
    #: serving-mesh opt-in (inference/serving.py MeshConfig): the paged
    #: hooks derive head counts from weight shapes and gather
    #: column-sharded outputs, so they run correctly as tp shards inside
    #: the engine's shard_map. Models whose paged hooks slice fused or
    #: interleaved projections (gpt's qkv) must NOT set this — a column
    #: shard of the fused weight would mix q/k/v.
    tp_serving = True

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head_weight = None
        else:
            init = I.Normal(std=config.initializer_range)
            self.lm_head_weight = annotate(
                self.create_parameter([config.hidden_size, config.vocab_size],
                                      dtype=config.dtype, default_initializer=init),
                "embed", "vocab")

    def _lm_head_w(self):
        """[hidden, vocab] projection — tied embedding transpose or lm_head."""
        return (self.model.embed_tokens_weight._data.T
                if self.lm_head_weight is None else self.lm_head_weight._data)

    @_scope("pt.lm_head")
    def logits(self, hidden):
        out = jnp.matmul(hidden, self._lm_head_w())
        if self.lm_head_weight is not None:
            # serving tp shard: an UNTIED lm_head is vocab-column-sharded, so
            # gather the full-vocab logits before sampling/argmax (no-op
            # outside a serving shard_map; tied heads ride the replicated
            # embedding and are already full-width)
            out = gather_output_shards(out)
        return constrain(out, "batch", "seq", "vocab")

    def forward(self, input_ids, labels=None, attn_bias=None):
        hidden = self.model(input_ids, attn_bias)
        logits = self.logits(hidden)
        if labels is None:
            return Tensor(logits) if not isinstance(logits, jax.core.Tracer) else logits
        loss = LlamaPretrainingCriterion.compute(logits, _raw(labels))
        return loss

    def paged_token_step(self, toks, caches, pos_vec):
        """Continuous-batching hook: ONE token per slot at per-slot positions.
        toks [b] int32, pos_vec [b] int32, caches from _init_paged_caches.
        Returns (logits [b, vocab] f32, caches).

        Contract the serving engine's fused mega-step leans on
        (inference/serving.py): inactive rows arrive at pos_vec == 0 with
        their table row pointing at a parking page — the dummy k/v append
        must land wherever THAT table maps (never a page another row
        shares), and the row's logits are computed but ignored. This body
        runs inside a lax.scan over all max_batch rows; everything here
        must stay shape-static in the row count."""
        cfg = self.config
        model = self.model
        x = jnp.take(model.embed_tokens_weight._data, toks[:, None], axis=0)
        tables = caches["tables"]
        page = caches["kv"][0][0].shape[2]
        max_len = tables.shape[1] * page
        cos_full, sin_full = _rope_cos_sin(max_len, cfg.head_dim,
                                           cfg.rope_theta, x.dtype)
        posc = jnp.clip(pos_vec, 0, max_len - 1)
        cos = cos_full[posc][:, None, :]
        sin = sin_full[posc][:, None, :]
        new_kv = []
        for layer, (kp, vp) in zip(model.layers, caches["kv"]):
            x, kp, vp = layer.paged_token_step(x, cos, sin, kp, vp, tables,
                                               pos_vec)
            new_kv.append((kp, vp))
        hidden = model.norm(x)
        hidden = hidden._data if isinstance(hidden, Tensor) else hidden
        logits = self.logits(hidden[:, -1:])
        return logits[:, -1].astype(jnp.float32), {"kv": new_kv,
                                                   "tables": tables}

    def paged_prefill_chunk(self, ids, caches, starts):
        """Serving hook: prefill ONE chunk per row at per-row absolute
        offsets, attending over the already-cached prefix (prefix-cache /
        chunked-prefill path — inference/serving.py). ids [b, s] int32,
        starts [b] int32; returns updated caches only (the first sampled
        token comes from the subsequent paged_token_step re-step, so no
        lm-head work here).

        Packed-rows contract (the fused engine's ``_run_pack``): several
        rows may carry the SAME sequence's table at different ``starts``
        (multiple chunks of one prompt in one call), plus parked dummy
        rows. Per layer, every row's k/v is appended BEFORE attention
        gathers — so a later chunk reads an earlier chunk's pages written
        in this very program; the absolute-position mask keeps the result
        bit-identical to sequential chunk calls (see
        ops.paged_prefill_attention). Every ``starts[b]`` is a multiple of
        the page (the engine's ``_run_pack`` holds its offsets to that), so
        the layers append by the page."""
        cfg = self.config
        model = self.model
        x = jnp.take(model.embed_tokens_weight._data, ids, axis=0)
        tables = caches["tables"]
        page = caches["kv"][0][0].shape[2]
        max_len = tables.shape[1] * page
        cos_full, sin_full = _rope_cos_sin(max_len, cfg.head_dim,
                                           cfg.rope_theta, x.dtype)
        s = ids.shape[1]
        positions = jnp.clip(starts[:, None] + jnp.arange(s)[None, :],
                             0, max_len - 1)
        cos = cos_full[positions]
        sin = sin_full[positions]
        new_kv = []
        for layer, (kp, vp) in zip(model.layers, caches["kv"]):
            x, kp, vp = layer.paged_prefill_chunk(x, cos, sin, kp, vp,
                                                  tables, starts,
                                                  page_aligned=True)
            new_kv.append((kp, vp))
        return {"kv": new_kv, "tables": tables}

    def paged_verify_step(self, toks, caches, pos_vec):
        """Speculative-decode VERIFY hook (inference/serving.py spec
        mega-step): score a K+1-token window per row in ONE pass.

        ``toks`` [b, s] int32 — per row the window
        ``[last_token, draft_1..draft_K]`` at absolute positions
        ``pos_vec[b] + i``; returns (logits [b, s, vocab] f32, caches) with
        the window's k/v appended. The body is the K-wide sibling of
        ``paged_token_step``: same embed/rope/layer math run through the
        chunk machinery (``paged_prefill_chunk`` layers over
        ``ops.paged_verify_attention``'s append-then-gather +
        absolute-position masking), plus the lm head over EVERY window
        position — so position i's logits match what a sequential
        ``paged_token_step`` at that position would compute given the same
        cache bytes (the greedy byte-identity the engine's in-graph
        accept/reject rests on). Honors the parked-row contract: inactive
        rows arrive at pos_vec == 0 over a parking-page table; their
        appends and logits are inert."""
        cfg = self.config
        model = self.model
        ids = toks
        x = jnp.take(model.embed_tokens_weight._data, ids, axis=0)
        tables = caches["tables"]
        page = caches["kv"][0][0].shape[2]
        max_len = tables.shape[1] * page
        cos_full, sin_full = _rope_cos_sin(max_len, cfg.head_dim,
                                           cfg.rope_theta, x.dtype)
        s = ids.shape[1]
        positions = jnp.clip(pos_vec[:, None] + jnp.arange(s)[None, :],
                             0, max_len - 1)
        cos = cos_full[positions]
        sin = sin_full[positions]
        new_kv = []
        for layer, (kp, vp) in zip(model.layers, caches["kv"]):
            x, kp, vp = layer.paged_prefill_chunk(x, cos, sin, kp, vp,
                                                  tables, pos_vec)
            new_kv.append((kp, vp))
        hidden = model.norm(x)
        hidden = hidden._data if isinstance(hidden, Tensor) else hidden
        logits = self.logits(hidden)
        return logits.astype(jnp.float32), {"kv": new_kv, "tables": tables}

    def remat_policy(self):
        """Engine hook: the jax.checkpoint policy for this model's blocks."""
        return remat_policy_of(self.config)

    def moe_aux_loss(self):
        """Sum of gate load-balance losses from the last forward (0 if dense).

        Collected as checkpoint outputs during LlamaModel.forward — safe under
        recompute (reading gate side channels here would leak remat tracers).
        """
        if self.config.num_experts <= 1:
            return 0.0
        return getattr(self.model, "_moe_aux", 0.0)

    def _decode_chunk(self, ids, caches, pos, pad_bias, pos_offset):
        if isinstance(caches, dict):  # paged-KV serving path
            hidden, caches = _decode_model_paged(self.model, ids, caches, pos)
        else:
            hidden, caches = _decode_model(self.model, ids, caches, pos,
                                           pad_bias, pos_offset)
        hidden = hidden._data if isinstance(hidden, Tensor) else hidden
        # lm head only on the position we sample from
        logits = self.logits(hidden[:, -1:])
        return logits[:, -1].astype(jnp.float32), caches

    def loss_fn(self, input_ids, labels):
        """Raw-array loss for jit'ed training steps."""
        hidden = self.model(input_ids)
        loss = self._lm_loss(hidden, labels)
        if self.config.num_experts > 1:
            loss = loss + self.config.moe_aux_weight * self.moe_aux_loss()
        return loss

    def _lm_loss(self, hidden, labels):
        """Shifted CE from final hidden states; fused-chunked by default."""
        hidden = hidden._data if isinstance(hidden, Tensor) else hidden
        if self.config.fused_ce:
            from ...ops.fused_ce import fused_linear_cross_entropy

            return fused_linear_cross_entropy(
                hidden, self._lm_head_w(), _raw(labels),
                chunk=self.config.fused_ce_chunk)
        return LlamaPretrainingCriterion.compute(self.logits(hidden),
                                                 _raw(labels))

    # ---- pipeline-parallel protocol (used by Engine when mesh has pp > 1) ----
    @property
    def pipeline_with_aux(self) -> bool:
        """Blocks emit a scalar aux output (MoE gate load-balance loss)."""
        return self.config.num_experts > 1

    def pipeline_blocks(self):
        """The homogeneous block stack to be sharded over the pp axis."""
        return list(self.model.layers)

    def pipeline_loss(self, input_ids, labels, run_blocks):
        """Loss with the decoder stack replaced by ``run_blocks(x, cos, sin)``.

        Embedding / final norm / lm-head run outside the pipeline (replicated
        over pp, sharded over the other axes) — the analogue of the reference
        putting embedding+head on first/last stages (pp_layers.py SharedLayerDesc),
        collapsed here because GSPMD dedupes replicated compute. ``run_blocks``
        may return ``(x, aux)`` — the per-microbatch-averaged MoE gate loss.
        """
        x, cos, sin = self.model.embed_and_rope(input_ids)
        res = run_blocks(x, cos, sin)
        x, aux = res if isinstance(res, tuple) else (res, None)
        x = self.model.norm(x)
        x = x._data if isinstance(x, Tensor) else x
        loss = self._lm_loss(x, labels)
        if aux is not None:
            loss = loss + self.config.moe_aux_weight * aux
        return loss

    def pipeline_block_fn(self, block):
        """Functional single-block forward for stacked-param execution."""
        tensors = [t for _, t in block.named_parameters()]
        with_aux = self.pipeline_with_aux

        def fn(param_arrays, x, cos, sin):
            from ...jit.api import _Swap

            with _Swap(tensors, param_arrays):
                y = block(x, cos, sin)
                if with_aux:
                    return y, _raw(block.mlp.get_loss())
                return y

        return fn


def _raw(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


class LlamaPretrainingCriterion(Layer):
    """Shifted causal-LM cross entropy, fp32 softmax (bf16-safe)."""

    @staticmethod
    def compute(logits, labels, ignore_index: int = -100):
        lg = logits[:, :-1, :].astype(jnp.float32)
        lb = labels[:, 1:]
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, lb[..., None].astype(jnp.int32), axis=-1)[..., 0]
        nll = logz - picked
        mask = (lb != ignore_index)
        nll = jnp.where(mask, nll, 0.0)
        return nll.sum() / jnp.maximum(mask.sum().astype(jnp.float32), 1.0)

    def forward(self, prediction_scores, masked_lm_labels):
        return Tensor(self.compute(_raw(prediction_scores), _raw(masked_lm_labels)))
