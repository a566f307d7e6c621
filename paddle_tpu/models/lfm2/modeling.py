"""LFM2-MoE family: gated short convolutions beside grouped-query attention
with QK-norm, leading dense SwiGLU layers, then routed SwiGLU experts behind a
sigmoid, bias-corrected router (published config: ``model_type`` ``lfm2_moe``).

A layer is ``h = x + Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; which
operator and which FFN a layer has follows from ``layer_types`` and
``num_dense_layers``:

  conv            ``[B, C, X] = split3(n W_in)``; ``u = B * X``;
                  ``c_t = sum_j k_j * u_{t-(L-1)+j}`` (depthwise, causal,
                  ``L = conv_L_cache``); ``Op = (C * c) W_out``. What a
                  sequence carries from token to token is its last ``L - 1``
                  ``u``: a fixed block, whatever its length.
  full_attention  q/k/v without bias, an RMSNorm over each head's dims on q
                  and k (gains shared by the heads) BEFORE rotary
                  (rotate-half over the whole head), causal softmax, ``W_o``.
  dense FFN       ``W2(silu(W1 m) * W3 m)``.
  expert FFN      ``DroplessMoE`` behind a ``SigmoidGate``
                  (incubate/distributed/models/moe): router in float32, the
                  expert bias in the choice only, gates renormalised over the
                  chosen, no capacity, no token dropped, no shared expert.

The head is tied to the embedding.

Serving. ``paged_token_step`` and ``paged_prefill_chunk`` keep
``models/llama``'s contracts (parked rows inert, append before gather,
shape-static in the row count); ``paged_verify_step`` raises
``LayerStateError`` (a state ring cannot take a rejected draft back). What a
layer keeps in ``caches["kv"]`` depends on its kind: an attention layer a
``(k_pages, v_pages)`` pair, a conv layer an ``ops.paged_attention.PageState``
whose ring holds, page by page, the last ``L`` ``u`` written in that page
(docs/SERVING.md "State that is not pages"). ``paged_token_step`` returns, in
``caches["counters"]``, the rows each expert of each expert layer got
(``moe_rows`` [expert layers, experts] int32).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...distributed.auto_parallel.logical_sharding import annotate
from ...incubate.distributed.models.moe import DroplessMoE, SigmoidGate
from ...nn import initializer as I
from ...nn.layer.layers import Layer, LayerList
from ..llama.modeling import _rope_cos_sin, _rotate_half

CONV, ATTN = "conv", "full_attention"


def _scope(name):
    return functools.partial(jax.named_call, name=name)


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


class Lfm2Config:
    """The published keys of ``lfm2_moe`` (``rope_theta`` flattened out of
    ``rope_parameters``), plus ``dtype``, ``initializer_range`` and the
    router's ``gate_norm_eps`` (the 1e-6 of the reference code)."""

    def __init__(self, vocab_size: int = 65536, hidden_size: int = 2048,
                 intermediate_size: int = 11776,
                 moe_intermediate_size: int = 1536,
                 num_hidden_layers: int = 40,
                 layer_types: Optional[Sequence[str]] = None,
                 num_dense_layers: int = 2,
                 num_attention_heads: int = 32, num_key_value_heads: int = 8,
                 num_experts: int = 64, num_experts_per_tok: int = 4,
                 norm_topk_prob: bool = True, use_expert_bias: bool = True,
                 routed_scaling_factor: float = 1.0, conv_L_cache: int = 3,
                 conv_bias: bool = False, norm_eps: float = 1e-5,
                 rope_theta: float = 1e6,
                 max_position_embeddings: int = 128000,
                 initializer_range: float = 0.02, gate_norm_eps: float = 1e-6,
                 dtype: str = "bfloat16"):
        if layer_types is None:
            layer_types = [ATTN if i >= 2 and (i - 2) % 4 == 0 else CONV
                           for i in range(num_hidden_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers or any(
                t not in (CONV, ATTN) for t in layer_types):
            raise ValueError(f"layer_types must name {num_hidden_layers} "
                             f"layers of {CONV!r} / {ATTN!r}: {layer_types}")
        if conv_bias:
            raise ValueError("conv_bias=True is not in the published family")
        if hidden_size % num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = layer_types
        self.num_dense_layers = num_dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.use_expert_bias = use_expert_bias
        self.routed_scaling_factor = routed_scaling_factor
        self.conv_L_cache = conv_L_cache
        self.norm_eps = norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.gate_norm_eps = gate_norm_eps
        self.dtype = dtype

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def tiny(cls, **over):
        kw = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, num_hidden_layers=5,
                  layer_types=[CONV, ATTN, CONV, CONV, CONV],
                  num_dense_layers=1, num_attention_heads=4,
                  num_key_value_heads=2, num_experts=8,
                  num_experts_per_tok=4, max_position_embeddings=256,
                  initializer_range=0.1, dtype="float32")
        kw.update(over)
        return cls(**kw)


class Lfm2RMSNorm(Layer):
    def __init__(self, width: int, eps: float, dtype: str):
        super().__init__()
        self.eps = eps
        self.weight = annotate(self.create_parameter(
            [width], dtype=dtype, default_initializer=I.Constant(1.0)),
            "norm")

    def forward(self, x):
        x = _raw(x)
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return ((xf * jax.lax.rsqrt(var + self.eps)).astype(x.dtype)
                * self.weight._data)


def _causal_conv(u, prev, kernel):
    """u [b, s, h] at consecutive positions, prev [b, L-1, h] the inputs
    before them (oldest first), kernel [h, L]: c_t = sum_j k_j u_{t-(L-1)+j}."""
    s, taps = u.shape[1], kernel.shape[1]
    full = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    return sum(kernel[:, j] * full[:, j:j + s] for j in range(taps))


class Lfm2ShortConv(Layer):
    """The gated short convolution. State: the last ``L - 1`` values of
    ``u = B * X`` (a ``PageState`` ring of ``L`` slots a page when served)."""

    def __init__(self, config: Lfm2Config):
        super().__init__()
        h, taps = config.hidden_size, config.conv_L_cache
        self.taps = taps
        init = I.Normal(std=config.initializer_range)
        mk = lambda shape: self.create_parameter(
            shape, dtype=config.dtype, default_initializer=init)
        self.in_proj_weight = annotate(mk([h, 3 * h]), "embed", "mlp")
        self.conv_weight = mk([h, taps])
        self.out_proj_weight = annotate(mk([h, h]), "mlp", "embed")

    def _gates(self, x):
        b_, c_, x_ = jnp.split(jnp.matmul(x, self.in_proj_weight._data), 3,
                               axis=-1)
        return b_ * x_, c_

    def _out(self, gate, conv):
        return jnp.matmul(gate * conv, self.out_proj_weight._data)

    @_scope("pt.conv")
    def forward(self, x):
        x = _raw(x)
        u, gate = self._gates(x)
        prev = jnp.zeros((x.shape[0], self.taps - 1, x.shape[2]), u.dtype)
        return self._out(gate, _causal_conv(u, prev, self.conv_weight._data))

    @_scope("pt.conv")
    def paged_chunk(self, x, state, tables, starts, valid):
        """x [b, s, h] at absolute positions ``starts[b] + i``; ``valid``
        [b, s] bool (False: a padded tail, which must leave no trace).
        Every row's ``u`` is written before any row reads the inputs before
        its chunk, so a later chunk of one prompt resumes from an earlier
        one written in this very program (``starts`` page-aligned, as the
        engine's chunks are)."""
        from ...ops.paged_attention import (page_state_keep_last,
                                            page_state_read,
                                            page_state_write)

        x = _raw(x)
        b, s, h = x.shape
        u, gate = self._gates(x)
        max_len = tables.shape[1] * state.page
        pos = jnp.clip(starts[:, None] + jnp.arange(s, dtype=jnp.int32),
                       0, max_len - 1)
        keep = page_state_keep_last(valid, pos % state.page, state.slots,
                                    state.page)
        seq_ids = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
        state = page_state_write(state, u.reshape(b * s, h), tables,
                                 pos.reshape(-1), seq_ids, keep.reshape(-1))
        prev = page_state_read(state, tables, starts, self.taps - 1)
        return self._out(gate, _causal_conv(u, prev,
                                            self.conv_weight._data)), state

    @_scope("pt.conv")
    def paged_token(self, x, state, tables, pos_vec):
        """One token a row at per-row positions. Position ``p`` reads the
        ring slots of ``p-1 .. p-(L-1)`` and writes slot ``p % L``: another
        one, so re-stepping a position is idempotent for the state."""
        from ...ops.paged_attention import page_state_read, page_state_write

        x = _raw(x)
        u, gate = self._gates(x)                       # [b, 1, h]
        prev = page_state_read(state, tables, pos_vec, self.taps - 1)
        state = page_state_write(state, u[:, 0], tables, pos_vec)
        return self._out(gate, _causal_conv(u, prev,
                                            self.conv_weight._data)), state


def _rope(x, cos, sin):
    """x [b, s, heads, d]; cos/sin [b, s, d] or [s, d]."""
    cos, sin = ((cos[:, :, None], sin[:, :, None]) if cos.ndim == 3
                else (cos[None, :, None], sin[None, :, None]))
    return x * cos + _rotate_half(x) * sin


class Lfm2Attention(Layer):
    def __init__(self, config: Lfm2Config):
        super().__init__()
        self.config = config
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        init = I.Normal(std=config.initializer_range)
        mk = lambda din, dout: self.create_parameter(
            [din, dout], dtype=config.dtype, default_initializer=init)
        self.q_proj_weight = annotate(mk(h, self.num_heads * hd),
                                      "embed", "heads")
        self.k_proj_weight = annotate(mk(h, self.num_kv_heads * hd),
                                      "embed", "heads")
        self.v_proj_weight = annotate(mk(h, self.num_kv_heads * hd),
                                      "embed", "heads")
        self.o_proj_weight = annotate(mk(self.num_heads * hd, h),
                                      "heads", "embed")
        self.q_norm = Lfm2RMSNorm(hd, config.norm_eps, config.dtype)
        self.k_norm = Lfm2RMSNorm(hd, config.norm_eps, config.dtype)

    def _qkv(self, x, cos, sin):
        b, s, _ = x.shape
        hd = self.config.head_dim
        q = jnp.matmul(x, self.q_proj_weight._data).reshape(b, s, -1, hd)
        k = jnp.matmul(x, self.k_proj_weight._data).reshape(b, s, -1, hd)
        v = jnp.matmul(x, self.v_proj_weight._data).reshape(b, s, -1, hd)
        return (_rope(self.q_norm(q), cos, sin),
                _rope(self.k_norm(k), cos, sin), v)

    @_scope("pt.attn")
    def forward(self, x, cos, sin):
        x = _raw(x)
        b, s, _ = x.shape
        q, k, v = self._qkv(x, cos, sin)
        rep = self.num_heads // self.num_kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
        sc = sc / math.sqrt(self.config.head_dim)
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        return jnp.matmul(out.reshape(b, s, -1), self.o_proj_weight._data)

    @_scope("pt.attn")
    def paged_chunk(self, x, cos, sin, k_pages, v_pages, tables, starts,
                    page_aligned=False):
        from ...ops.paged_attention import (append_paged_chunk,
                                            paged_prefill_attention)

        x = _raw(x)
        b, s, _ = x.shape
        q, k, v = self._qkv(x, cos, sin)
        k_pages, v_pages = append_paged_chunk(
            k_pages, v_pages, k, v, tables, starts, page_aligned)
        out = paged_prefill_attention(q, k_pages, v_pages, tables, starts)
        return (jnp.matmul(out.reshape(b, s, -1), self.o_proj_weight._data),
                k_pages, v_pages)

    @_scope("pt.attn")
    def paged_token(self, x, cos, sin, k_pages, v_pages, tables, pos_vec):
        from ...ops.paged_attention import (append_paged_kv,
                                            paged_decode_attention)

        x = _raw(x)
        b = x.shape[0]
        q, k, v = self._qkv(x, cos, sin)
        k_pages, v_pages = append_paged_kv(
            k_pages, v_pages, k[:, 0], v[:, 0], tables, pos_vec)
        out = paged_decode_attention(q[:, 0], k_pages, v_pages, tables,
                                     pos_vec + 1)
        return (jnp.matmul(out.reshape(b, 1, -1), self.o_proj_weight._data),
                k_pages, v_pages)


class Lfm2MLP(Layer):
    def __init__(self, config: Lfm2Config):
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
        x = _raw(x)
        act = (jax.nn.silu(jnp.matmul(x, self.gate_proj_weight._data))
               * jnp.matmul(x, self.up_proj_weight._data))
        return jnp.matmul(act, self.down_proj_weight._data)


class Lfm2DecoderLayer(Layer):
    def __init__(self, config: Lfm2Config, index: int):
        super().__init__()
        self.kind = config.layer_types[index]
        self.operator_norm = Lfm2RMSNorm(config.hidden_size, config.norm_eps,
                                         config.dtype)
        if self.kind == CONV:
            self.conv = Lfm2ShortConv(config)
        else:
            self.self_attn = Lfm2Attention(config)
        self.ffn_norm = Lfm2RMSNorm(config.hidden_size, config.norm_eps,
                                    config.dtype)
        self.routed = index >= config.num_dense_layers
        if self.routed:
            self.feed_forward = DroplessMoE(
                config.hidden_size, config.num_experts,
                config.moe_intermediate_size,
                gate=SigmoidGate(
                    config.hidden_size, config.num_experts,
                    topk=config.num_experts_per_tok,
                    use_bias=config.use_expert_bias,
                    renormalize=config.norm_topk_prob,
                    scaling=config.routed_scaling_factor,
                    norm_eps=config.gate_norm_eps,
                    initializer_range=config.initializer_range),
                dtype=config.dtype,
                initializer_range=config.initializer_range)
        else:
            self.feed_forward = Lfm2MLP(config)

    def ffn(self, h):
        """``h + FFN(RMSNorm(h))`` and the rows each expert got (None for a
        dense layer)."""
        m = self.ffn_norm(h)
        if self.routed:
            y, rows = self.feed_forward(m, with_rows=True)
            return h + y, rows
        return h + self.feed_forward(m), None


class Lfm2Model(Layer):
    def __init__(self, config: Lfm2Config):
        super().__init__()
        self.config = config
        self.embed_tokens_weight = annotate(self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=I.Normal(std=config.initializer_range)),
            "vocab_in", "embed")
        self.layers = LayerList([Lfm2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = Lfm2RMSNorm(config.hidden_size, config.norm_eps,
                                config.dtype)


def _page_size(kv) -> int:
    """Tokens a page, from whatever the first layer keeps."""
    from ...ops.paged_attention import PageState

    first = kv[0]
    return first.page if isinstance(first, PageState) else first[0].shape[2]


class Lfm2ForCausalLM(Layer):
    """``Lfm2Model`` with the tied head and the serving engine's hooks."""

    def __init__(self, config: Lfm2Config):
        super().__init__()
        self.config = config
        self.model = Lfm2Model(config)

    @_scope("pt.lm_head")
    def logits(self, hidden):
        return jnp.matmul(hidden, self.model.embed_tokens_weight._data.T)

    def forward(self, input_ids):
        """Logits [b, s, vocab] float32 of ``input_ids`` [b, s], no cache."""
        ids = _raw(input_ids)
        cfg, model = self.config, self.model
        x = jnp.take(model.embed_tokens_weight._data, ids, axis=0)
        cos, sin = _rope_cos_sin(ids.shape[1], cfg.head_dim, cfg.rope_theta,
                                 x.dtype)
        for layer in model.layers:
            n = layer.operator_norm(x)
            x = x + (layer.conv(n) if layer.kind == CONV
                     else layer.self_attn(n, cos, sin))
            x, _ = layer.ffn(x)
        return self.logits(model.norm(x)).astype(jnp.float32)

    # ---- serving hooks (contracts: models/llama/modeling.py) --------------
    def _init_paged_caches(self, b, max_len, page_size=64, num_blocks=None,
                           kv_dtype=None, kv_shards=1):
        """What each layer keeps, for the engine: an attention layer a
        ``(k_pages, v_pages)`` pair [pages, kv_heads, page, head_dim] in
        the form ``kv_pool_shape`` gives (heads of 64: two to a 128-lane
        row), a conv layer a ``PageState`` ring [pages, L, hidden]; the
        pages asked for rounded up to the tile's rows (``pool_pages``: the
        rings' scatter then collapses ``[L, pages]`` without a copy)."""
        from ...ops.paged_attention import (PageState, kv_pool_shape,
                                            pool_pages)

        cfg = self.config
        if kv_dtype not in (None, "param"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}: a state "
                             f"ring has no int8 block format")
        if page_size < cfg.conv_L_cache:
            raise ValueError(f"page_size {page_size} is shorter than the "
                             f"convolution ({cfg.conv_L_cache} taps)")
        dtype = self.model.embed_tokens_weight._data.dtype
        maxp = -(-max_len // page_size)
        npages = b * maxp if num_blocks is None else int(num_blocks)
        if npages < b * maxp:
            raise ValueError(f"num_blocks {npages} < {b * maxp} — the pool "
                             "cannot back every slot's table")
        npages = pool_pages(npages, dtype)
        kv = []
        for kind in cfg.layer_types:
            if kind == CONV:
                kv.append(PageState(jnp.zeros(
                    (npages, cfg.conv_L_cache, cfg.hidden_size), dtype),
                    page_size))
            else:
                shape = kv_pool_shape(npages, cfg.num_key_value_heads,
                                      page_size, cfg.head_dim, dtype,
                                      shards=kv_shards)
                kv.append((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)))
        tables = jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp)
        return {"kv": kv, "tables": tables}

    def _rope_rows(self, kv, tables, positions, dtype):
        cfg = self.config
        max_len = tables.shape[1] * _page_size(kv)
        cos, sin = _rope_cos_sin(max_len, cfg.head_dim, cfg.rope_theta, dtype)
        pos = jnp.clip(positions, 0, max_len - 1)
        return cos[pos], sin[pos]

    def paged_token_step(self, toks, caches, pos_vec):
        """ONE token per row at per-row positions; returns (logits [b, vocab]
        f32, caches). Parked rows (``pos_vec == 0`` over a parking-page
        table) write the parking page's ring and K/V only. ``caches`` comes
        back with ``"counters": {"moe_rows": [expert layers, experts]}``."""
        model = self.model
        kv, tables = caches["kv"], caches["tables"]
        x = jnp.take(model.embed_tokens_weight._data, toks[:, None], axis=0)
        cos, sin = self._rope_rows(kv, tables, pos_vec[:, None], x.dtype)
        new_kv, rows = [], []
        for layer, entry in zip(model.layers, kv):
            n = layer.operator_norm(x)
            if layer.kind == CONV:
                a, entry = layer.conv.paged_token(n, entry, tables, pos_vec)
            else:
                a, kp, vp = layer.self_attn.paged_token(
                    n, cos, sin, entry[0], entry[1], tables, pos_vec)
                entry = (kp, vp)
            new_kv.append(entry)
            x, r = layer.ffn(x + a)
            if r is not None:
                rows.append(r)
        logits = self.logits(model.norm(x)[:, -1])
        out = {"kv": new_kv, "tables": tables}
        if rows:
            out["counters"] = {"moe_rows": jnp.stack(rows)}
        return logits.astype(jnp.float32), out

    def paged_prefill_chunk(self, ids, caches, starts):
        """Prefill ONE chunk per row at per-row page-aligned offsets (the
        packed-rows contract of ``models/llama``: several rows may carry one
        sequence's table at different ``starts``; every row's K/V and conv
        inputs are written before any row reads). ``caches["valid"]`` [b]
        int32, when the engine gives it, is each row's count of real tokens:
        the zero-padded tail of a last chunk writes no conv state."""
        model = self.model
        kv, tables = caches["kv"], caches["tables"]
        b, s = ids.shape
        x = jnp.take(model.embed_tokens_weight._data, ids, axis=0)
        positions = starts[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        cos, sin = self._rope_rows(kv, tables, positions, x.dtype)
        valid = caches.get("valid")
        valid = (jnp.ones((b, s), bool) if valid is None else
                 jnp.arange(s)[None, :] < valid[:, None])
        new_kv = []
        for layer, entry in zip(model.layers, kv):
            n = layer.operator_norm(x)
            if layer.kind == CONV:
                a, entry = layer.conv.paged_chunk(n, entry, tables, starts,
                                                  valid)
            else:
                a, kp, vp = layer.self_attn.paged_chunk(
                    n, cos, sin, entry[0], entry[1], tables, starts,
                    page_aligned=True)
                entry = (kp, vp)
            new_kv.append(entry)
            x, _ = layer.ffn(x + a)
        return {"kv": new_kv, "tables": tables}

    def paged_verify_step(self, toks, caches, pos_vec):
        """Refused: a conv ring has no room to take a rejected draft back
        (position ``p``'s slot is ``p % L``), so a verify window over state
        layers would lose state silently. The engine refuses speculative
        decoding over them with the same error."""
        from ...ops.paged_attention import LayerStateError

        raise LayerStateError(
            "PT-SRV-009: Lfm2ForCausalLM keeps layers of kind 'state' "
            "(PageState); a verify window cannot take a rejected draft back "
            "out of a state ring")
