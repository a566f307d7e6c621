"""GPT family (parity anchor: the reference's 3D-hybrid GPT tests,
/root/reference/test/auto_parallel/ GPT cases; architecture = pre-LN GPT-2/3:
learned positions, LayerNorm, GELU MLP, MHA).

Same mesh-aware design as Llama: logical-axis-annotated params, GSPMD sharding.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...distributed.auto_parallel.logical_sharding import annotate, constrain
from ...nn import initializer as I
from ...nn.layer.layers import Layer, LayerList
from ..generation_utils import GenerationMixin
from ..llama.modeling import _attention, _raw


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, intermediate_size=None,
                 num_hidden_layers=12, num_attention_heads=12,
                 max_position_embeddings=1024, layer_norm_eps=1e-5,
                 initializer_range=0.02, dtype="float32", recompute=False,
                 use_flash_attention=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.layer_norm_eps = layer_norm_eps
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.recompute = recompute
        self.use_flash_attention = use_flash_attention

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **over):
        d = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                 num_attention_heads=4, max_position_embeddings=128)
        d.update(over)
        return cls(**d)


class GPTLayerNorm(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.eps = config.layer_norm_eps
        self.weight = annotate(self.create_parameter(
            [config.hidden_size], dtype=config.dtype,
            default_initializer=I.Constant(1.0)), "norm")
        self.bias = annotate(self.create_parameter(
            [config.hidden_size], dtype=config.dtype, is_bias=True), "norm")

    def forward(self, x):
        x = _raw(x)
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = ((xf - mu) * jax.lax.rsqrt(var + self.eps)).astype(x.dtype)
        return out * self.weight._data + self.bias._data


class GPTAttention(Layer):
    def decode_step(self, x, k_cache, v_cache, pos, pad_bias=None):
        """KV-cache attention for generation (prefill AND decode)."""
        from ..generation_utils import causal_cache_bias
        from ...nn.functional.flash_attention import _xla_attention

        x = _raw(x)
        b, s, h = x.shape
        hd = self.config.head_dim
        qkv = jnp.matmul(x, self.qkv_weight._data) + self.qkv_bias._data
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, hd)
        k = k.reshape(b, s, self.num_heads, hd)
        v = v.reshape(b, s, self.num_heads, hd)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
        bias = causal_cache_bias(k_cache, pos, s, pad_bias)
        out = _xla_attention(q, k_cache, v_cache, bias=bias, causal=False)
        out = out.reshape(b, s, h)
        return (jnp.matmul(out, self.out_weight._data)
                + self.out_bias._data, k_cache, v_cache)

    def paged_decode_step(self, x, k_pages, v_pages, tables, pos):
        """Paged-KV generation step (serving suite) — see the llama analogue."""
        from ...ops.flash_attention import flash_attention
        from ...ops.paged_attention import append_paged_kv, paged_decode_attention

        x = _raw(x)
        b, s, h = x.shape
        hd = self.config.head_dim
        qkv = jnp.matmul(x, self.qkv_weight._data) + self.qkv_bias._data
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, hd)
        k = k.reshape(b, s, self.num_heads, hd)
        v = v.reshape(b, s, self.num_heads, hd)
        seq_ids = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
        positions = jnp.tile(pos + jnp.arange(s, dtype=jnp.int32), b)
        k_pages, v_pages = append_paged_kv(
            k_pages, v_pages, k.reshape(b * s, self.num_heads, hd),
            v.reshape(b * s, self.num_heads, hd), tables, positions, seq_ids)
        if s == 1:
            ctx = jnp.full((b,), pos + 1, jnp.int32)
            out = paged_decode_attention(q[:, 0], k_pages, v_pages, tables,
                                         ctx)[:, None]
        else:
            out = flash_attention(q, k, v, causal=True)
        out = out.reshape(b, s, h)
        return (jnp.matmul(out, self.out_weight._data)
                + self.out_bias._data, k_pages, v_pages)

    def paged_prefill_chunk(self, x, k_pages, v_pages, tables, starts,
                            page_aligned=False):
        """Prefill CHUNK at per-row absolute offsets over cached history
        (prefix-cache / chunked-prefill serving path) — llama analogue,
        ``page_aligned`` included."""
        from ...ops.paged_attention import (append_paged_chunk,
                                            paged_prefill_attention)

        x = _raw(x)
        b, s, h = x.shape
        hd = self.config.head_dim
        qkv = jnp.matmul(x, self.qkv_weight._data) + self.qkv_bias._data
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, hd)
        k = k.reshape(b, s, self.num_heads, hd)
        v = v.reshape(b, s, self.num_heads, hd)
        k_pages, v_pages = append_paged_chunk(
            k_pages, v_pages, k, v, tables, starts, page_aligned)
        out = paged_prefill_attention(q, k_pages, v_pages, tables, starts)
        out = out.reshape(b, s, h)
        return (jnp.matmul(out, self.out_weight._data)
                + self.out_bias._data, k_pages, v_pages)

    def paged_token_step(self, x, k_pages, v_pages, tables, pos_vec):
        """ONE token per row at PER-ROW positions (continuous batching)."""
        from ...ops.paged_attention import append_paged_kv, paged_decode_attention

        x = _raw(x)
        b = x.shape[0]
        h = x.shape[-1]
        hd = self.config.head_dim
        qkv = jnp.matmul(x, self.qkv_weight._data) + self.qkv_bias._data
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, 1, self.num_heads, hd)
        k = k.reshape(b, 1, self.num_heads, hd)
        v = v.reshape(b, 1, self.num_heads, hd)
        k_pages, v_pages = append_paged_kv(
            k_pages, v_pages, k[:, 0], v[:, 0], tables, pos_vec)
        out = paged_decode_attention(q[:, 0], k_pages, v_pages, tables,
                                     pos_vec + 1)
        out = out.reshape(b, 1, h)
        return (jnp.matmul(out, self.out_weight._data)
                + self.out_bias._data, k_pages, v_pages)

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        init = I.Normal(std=config.initializer_range)
        mk = lambda shape, axes: annotate(self.create_parameter(
            shape, dtype=config.dtype, default_initializer=init), *axes)
        self.qkv_weight = mk([h, 3 * h], ("embed", "heads"))
        self.qkv_bias = annotate(self.create_parameter(
            [3 * h], dtype=config.dtype, is_bias=True), "heads")
        self.out_weight = mk([h, h], ("heads", "embed"))
        self.out_bias = annotate(self.create_parameter(
            [h], dtype=config.dtype, is_bias=True), "norm")

    def forward(self, hidden):
        x = _raw(hidden)
        b, s, h = x.shape
        hd = self.config.head_dim
        qkv = jnp.matmul(x, self.qkv_weight._data) + self.qkv_bias._data
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, self.num_heads, hd)
        k = k.reshape(b, s, self.num_heads, hd)
        v = v.reshape(b, s, self.num_heads, hd)
        q = constrain(q, "batch", "seq", "heads", "head_dim")
        out = _attention(q, k, v, self.config)
        out = out.reshape(b, s, h)
        return jnp.matmul(out, self.out_weight._data) + self.out_bias._data


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        init = I.Normal(std=config.initializer_range)
        self.fc_weight = annotate(self.create_parameter(
            [h, m], dtype=config.dtype, default_initializer=init), "embed", "mlp")
        self.fc_bias = annotate(self.create_parameter(
            [m], dtype=config.dtype, is_bias=True), "mlp")
        self.proj_weight = annotate(self.create_parameter(
            [m, h], dtype=config.dtype, default_initializer=init), "mlp", "embed")
        self.proj_bias = annotate(self.create_parameter(
            [h], dtype=config.dtype, is_bias=True), "norm")

    def forward(self, x):
        x = _raw(x)
        a = jax.nn.gelu(jnp.matmul(x, self.fc_weight._data) + self.fc_bias._data)
        a = constrain(a, "batch", "seq", "mlp")
        return jnp.matmul(a, self.proj_weight._data) + self.proj_bias._data


class GPTDecoderLayer(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = GPTLayerNorm(config)
        self.attn = GPTAttention(config)
        self.ln_2 = GPTLayerNorm(config)
        self.mlp = GPTMLP(config)

    def forward(self, hidden):
        x = _raw(hidden)
        x = x + self.attn(self.ln_1(x))
        x = x + self.mlp(self.ln_2(x))
        return constrain(x, "batch", "seq", "embed")


    def paged_decode_step(self, hidden, k_pages, v_pages, tables, pos):
        x = _raw(hidden)
        a, k_pages, v_pages = self.attn.paged_decode_step(
            self.ln_1(x), k_pages, v_pages, tables, pos)
        x = x + a
        x = x + _raw(self.mlp(self.ln_2(x)))
        return x, k_pages, v_pages

    def paged_token_step(self, hidden, k_pages, v_pages, tables, pos_vec):
        x = _raw(hidden)
        a, k_pages, v_pages = self.attn.paged_token_step(
            self.ln_1(x), k_pages, v_pages, tables, pos_vec)
        x = x + a
        x = x + _raw(self.mlp(self.ln_2(x)))
        return x, k_pages, v_pages

    def paged_prefill_chunk(self, hidden, k_pages, v_pages, tables, starts,
                            page_aligned=False):
        x = _raw(hidden)
        a, k_pages, v_pages = self.attn.paged_prefill_chunk(
            self.ln_1(x), k_pages, v_pages, tables, starts, page_aligned)
        x = x + a
        x = x + _raw(self.mlp(self.ln_2(x)))
        return x, k_pages, v_pages

    def decode_step(self, hidden, k_cache, v_cache, pos, pad_bias=None):
        x = _raw(hidden)
        a, k_cache, v_cache = self.attn.decode_step(
            self.ln_1(x), k_cache, v_cache, pos, pad_bias)
        x = x + a
        x = x + self.mlp(self.ln_2(x))
        return x, k_cache, v_cache


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        init = I.Normal(std=config.initializer_range)
        self.wte = annotate(self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=init), "vocab_in", "embed")
        self.wpe = annotate(self.create_parameter(
            [config.max_position_embeddings, config.hidden_size],
            dtype=config.dtype, default_initializer=init), "seq", "embed")
        self.layers = LayerList([GPTDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.ln_f = GPTLayerNorm(config)

    def forward(self, input_ids):
        ids = _raw(input_ids)
        table = constrain(self.wte._data, None, None)
        x = jnp.take(table, ids, axis=0) + self.wpe._data[: ids.shape[1]]
        x = constrain(x, "batch", "seq", "embed")
        remat = self.config.recompute and isinstance(x, jax.core.Tracer)
        for layer in self.layers:
            if remat:
                x = jax.checkpoint(lambda h, lyr=layer: lyr(h))(x)
            else:
                x = layer(x)
        return self.ln_f(x)


class GPTForCausalLM(GenerationMixin, Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)

    def forward(self, input_ids, labels=None):
        from ..llama.modeling import LlamaPretrainingCriterion

        hidden = self.gpt(input_ids)
        logits = jnp.matmul(hidden, self.gpt.wte._data.T)
        logits = constrain(logits, "batch", "seq", "vocab")
        if labels is None:
            return Tensor(logits) if not isinstance(logits, jax.core.Tracer) else logits
        return LlamaPretrainingCriterion.compute(logits, _raw(labels))

    def loss_fn(self, input_ids, labels):
        return self.forward(input_ids, labels)


    # ---- generation hooks (GenerationMixin; default _init_caches) ----
    def _validate_generate(self, prompt_len, total_len):
        if total_len > self.config.max_position_embeddings:
            raise ValueError(
                f"GPT learned position table holds "
                f"{self.config.max_position_embeddings} positions; prompt + "
                f"max_new_tokens = {total_len} exceeds it")

    def paged_token_step(self, toks, caches, pos_vec):
        """Continuous-batching hook (see inference/serving.py): one token per
        slot at per-slot positions. Same parked-row contract as the llama
        hook: inactive rows run at pos_vec == 0 over a parking-page table
        (their dummy append and logits are inert), and the body stays
        shape-static in the row count — the fused mega-step scans it over
        all max_batch rows."""
        cfg = self.config
        posc = jnp.clip(pos_vec, 0, cfg.max_position_embeddings - 1)
        x = (jnp.take(self.gpt.wte._data, toks[:, None], axis=0)
             + self.gpt.wpe._data[posc][:, None])
        tables = caches["tables"]
        new_kv = []
        for layer, (kp, vp) in zip(self.gpt.layers, caches["kv"]):
            x, kp, vp = layer.paged_token_step(x, kp, vp, tables, pos_vec)
            new_kv.append((kp, vp))
        hidden = _raw(self.gpt.ln_f(x))
        logits = jnp.matmul(hidden[:, -1], self.gpt.wte._data.T)
        return logits.astype(jnp.float32), {"kv": new_kv, "tables": tables}

    def paged_prefill_chunk(self, ids, caches, starts):
        """Serving hook (see the llama analogue): one prefill chunk per row
        at per-row absolute offsets over cached history; returns caches.
        Honors the packed-rows contract (``_run_pack``): rows may share
        one sequence's table at different starts, every one a multiple
        of the page, and k/v appends land before any row's attention
        gathers per layer."""
        ids = _raw(ids)
        b, s = ids.shape
        positions = jnp.clip(starts[:, None] + jnp.arange(s)[None, :], 0,
                             self.config.max_position_embeddings - 1)
        x = (jnp.take(self.gpt.wte._data, ids, axis=0)
             + self.gpt.wpe._data[positions])
        tables = caches["tables"]
        new_kv = []
        for layer, (kp, vp) in zip(self.gpt.layers, caches["kv"]):
            x, kp, vp = layer.paged_prefill_chunk(x, kp, vp, tables, starts,
                                                  page_aligned=True)
            new_kv.append((kp, vp))
        return {"kv": new_kv, "tables": tables}

    def paged_verify_step(self, toks, caches, pos_vec):
        """Speculative-decode VERIFY hook (llama analogue — see
        ``LlamaForCausalLM.paged_verify_step``): one K+1-token window per
        row at absolute positions ``pos_vec[b] + i`` through the chunk
        machinery, with logits over EVERY window position for the
        engine's in-graph accept/reject. Parked rows are inert."""
        ids = _raw(toks)
        b, s = ids.shape
        positions = jnp.clip(pos_vec[:, None] + jnp.arange(s)[None, :], 0,
                             self.config.max_position_embeddings - 1)
        x = (jnp.take(self.gpt.wte._data, ids, axis=0)
             + self.gpt.wpe._data[positions])
        tables = caches["tables"]
        new_kv = []
        for layer, (kp, vp) in zip(self.gpt.layers, caches["kv"]):
            x, kp, vp = layer.paged_prefill_chunk(x, kp, vp, tables, pos_vec)
            new_kv.append((kp, vp))
        hidden = _raw(self.gpt.ln_f(x))
        logits = jnp.matmul(hidden, self.gpt.wte._data.T)
        return logits.astype(jnp.float32), {"kv": new_kv, "tables": tables}

    def _decode_chunk(self, ids, caches, pos, pad_bias, pos_offset):
        ids = _raw(ids)
        b, s = ids.shape
        x = jnp.take(self.gpt.wte._data, ids, axis=0)
        if pos_offset is None:
            wpe = jax.lax.dynamic_slice_in_dim(self.gpt.wpe._data, pos, s, 0)
            x = x + wpe[None]
        else:
            positions = jnp.clip(pos + jnp.arange(s)[None, :]
                                 - pos_offset[:, None], 0,
                                 self.config.max_position_embeddings - 1)
            x = x + self.gpt.wpe._data[positions]
        if isinstance(caches, dict):  # paged-KV serving path
            tables = caches["tables"]
            new_kv = []
            for layer, (kp, vp) in zip(self.gpt.layers, caches["kv"]):
                x, kp, vp = layer.paged_decode_step(x, kp, vp, tables, pos)
                new_kv.append((kp, vp))
            new_caches = {"kv": new_kv, "tables": tables}
        else:
            new_caches = []
            for layer, (kc, vc) in zip(self.gpt.layers, caches):
                x, kc, vc = layer.decode_step(x, kc, vc, pos, pad_bias)
                new_caches.append((kc, vc))
        hidden = _raw(self.gpt.ln_f(x))
        logits = jnp.matmul(hidden[:, -1], self.gpt.wte._data.T)
        return logits.astype(jnp.float32), new_caches
