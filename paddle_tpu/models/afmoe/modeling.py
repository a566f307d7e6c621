"""AFMoE family (published config: ``model_type`` ``afmoe``, Trinity): window
attention with rotary three layers in four beside full attention without
rotary, a sigmoid gate on the attention output, four norms a layer, leading
dense SwiGLU layers, then routed SwiGLU experts behind a sigmoid router
beside a shared expert. With ``d`` the hidden size and
``n(x; w) = x / sqrt(mean(x^2) + eps) * w``:

  embed     ``x = E[ids] * sqrt(d)`` (``mup_enabled``); nothing else is
            scaled at inference.
  attention ``a = n(x; w_in)``; q, k, v and a gate ``g`` (as wide as q)
            without bias; q and k through an RMSNorm over each head's dims
            (``models/lfm2``'s); rotary (rotate-half, the whole head) on a
            ``sliding_attention`` layer ONLY; causal softmax of
            ``q k^T / sqrt(head_dim)``, a sliding layer over the last
            ``sliding_window`` positions (the query's own among them);
            ``y = (o * sigmoid(g)) W_o``; ``x = x + n(y; w_post_attn)``.
  FFN       ``b = n(x; w_pre_mlp)``; the first ``num_dense_layers`` layers
            ``W_down(silu(W_gate b) * W_up b)``; the others ``DroplessMoE``
            behind a ``SigmoidGate`` (router in float32, the expert bias in
            the choice only, gates ``s / (sum s + 1e-20) * route_scale``)
            over SwiGLU experts, plus a shared SwiGLU expert
            ``num_shared_experts`` times as wide on every token;
            ``x = x + n(m; w_post_mlp)``. The layer routes over
            ``num_experts`` and holds ``experts_held`` of them (a chip's
            share of an expert-parallel layer, as ``models/nemotron_h``).
  head      ``n(x; w_f) W_head``, untied.

Serving. ONE chunk-shaped hook a block, ``AfmoeAttention.paged``: rows of
``s`` positions from per-row ``starts``, with the layer kind's window. The
packed prefill chunk is that hook (``page_aligned``), the token step the
hook at width 1 (``token=True``: ``append_paged_kv`` and the decode kernel
``pt_paged_decode``, which starts its walk at the window's first page), and
a verify window would be the hook at ``pos - 1``; ``paged_verify_step``
raises ``LayerStateError`` all the same, because the engine refuses
speculative decoding over page groups. ``kv_groups()`` declares the two
kinds, ``("full", None)`` first, and ``_init_paged_caches(group_blocks=)``
gives each kind's layers a pool of that group's size; ``caches["tables"]``
is then one table a group and a layer reads its own
(docs/SERVING.md "Window and full layers: a page group a kind").
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...distributed.auto_parallel.logical_sharding import annotate
from ...incubate.distributed.models.moe import DroplessMoE, SigmoidGate
from ...nn import initializer as I
from ...nn.layer.layers import Layer, LayerList
from ..lfm2.modeling import Lfm2RMSNorm
from ..llama.modeling import _rotate_half

SLIDING, FULL = "sliding_attention", "full_attention"


def _scope(name):
    return functools.partial(jax.named_call, name=name)


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


class AfmoeConfig:
    """The published keys of ``afmoe`` that shape the model, plus
    ``experts_held`` (``(first, count)`` of the routed experts this build
    holds; None: all), ``dtype`` and ``initializer_range``."""

    def __init__(self, vocab_size: int = 200192, hidden_size: int = 2048,
                 intermediate_size: int = 6144,
                 moe_intermediate_size: int = 1024,
                 num_hidden_layers: int = 32,
                 layer_types: Optional[Sequence[str]] = None,
                 num_dense_layers: int = 2, num_attention_heads: int = 32,
                 num_key_value_heads: int = 4, head_dim: int = 128,
                 sliding_window: int = 2048, num_experts: int = 128,
                 experts_held: Optional[Tuple[int, int]] = None,
                 num_experts_per_tok: int = 8, num_shared_experts: int = 1,
                 route_norm: bool = True, route_scale: float = 2.826,
                 rms_norm_eps: float = 1e-5, rope_theta: float = 10000.0,
                 mup_enabled: bool = True,
                 max_position_embeddings: int = 131072,
                 initializer_range: float = 0.02, dtype: str = "bfloat16"):
        if layer_types is None:
            layer_types = [FULL if i % 4 == 3 else SLIDING
                           for i in range(num_hidden_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers or any(
                t not in (SLIDING, FULL) for t in layer_types):
            raise ValueError(f"layer_types must name {num_hidden_layers} "
                             f"layers of {SLIDING!r} / {FULL!r}: "
                             f"{layer_types}")
        if num_attention_heads % num_key_value_heads:
            raise ValueError("heads must divide into their groups")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = layer_types
        self.num_dense_layers = num_dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = int(sliding_window)
        self.num_experts = num_experts
        self.experts_held = ((0, num_experts) if experts_held is None
                             else tuple(int(v) for v in experts_held))
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.mup_enabled = mup_enabled
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.dtype = dtype

    @classmethod
    def tiny(cls, **over):
        kw = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_hidden_layers=5,
                  layer_types=[SLIDING, SLIDING, SLIDING, FULL, SLIDING],
                  num_dense_layers=1, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, sliding_window=16,
                  num_experts=8, num_experts_per_tok=3,
                  max_position_embeddings=256, initializer_range=0.1,
                  dtype="float32")
        kw.update(over)
        return cls(**kw)


def _norm(config: AfmoeConfig, width: int) -> Lfm2RMSNorm:
    return Lfm2RMSNorm(width, config.rms_norm_eps, config.dtype)


def _rope(x, positions, theta: float):
    """Rotate-half rotary over the whole head: x [b, s, heads, d] at
    ``positions`` [b, s]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv      # [b, s, d/2]
    ang = jnp.concatenate([ang, ang], -1)[:, :, None, :]
    return (x * jnp.cos(ang).astype(x.dtype)
            + _rotate_half(x) * jnp.sin(ang).astype(x.dtype))


class AfmoeAttention(Layer):
    """Grouped-query attention of one kind (``window``: the sliding
    layers'; None: a full layer, which also has no rotary), QK-norm, and a
    sigmoid gate on the output before ``o_proj``."""

    def __init__(self, config: AfmoeConfig, kind: str):
        super().__init__()
        self.config = config
        self.kind = kind
        self.window = config.sliding_window if kind == SLIDING else None
        self.rotary = kind == SLIDING
        self.scope = "pt.attn.window" if kind == SLIDING else "pt.attn.full"
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
        self.gate_proj_weight = annotate(mk(h, self.num_heads * hd),
                                         "embed", "heads")
        self.o_proj_weight = annotate(mk(self.num_heads * hd, h),
                                      "heads", "embed")
        self.q_norm = _norm(config, hd)
        self.k_norm = _norm(config, hd)

    def _qkv(self, x, positions):
        b, s, _ = x.shape
        hd = self.config.head_dim
        q = self.q_norm(jnp.matmul(x, self.q_proj_weight._data)
                        .reshape(b, s, -1, hd))
        k = self.k_norm(jnp.matmul(x, self.k_proj_weight._data)
                        .reshape(b, s, -1, hd))
        v = jnp.matmul(x, self.v_proj_weight._data).reshape(b, s, -1, hd)
        if self.rotary:                        # by kind
            theta = self.config.rope_theta
            q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        return q, k, v

    def _out(self, x, o):
        """``(o * sigmoid(g)) W_o`` for o [b, s, heads * d]."""
        with jax.named_scope("pt.attn.gate"):
            g = jax.nn.sigmoid(jnp.matmul(x, self.gate_proj_weight._data)
                               .astype(jnp.float32))
            o = (o.astype(jnp.float32) * g).astype(x.dtype)
        return jnp.matmul(o, self.o_proj_weight._data)

    @_scope("pt.attn")
    def forward(self, x):
        x = _raw(x)
        b, s, _ = x.shape
        with jax.named_scope(self.scope):
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
            q, k, v = self._qkv(x, pos)
            rep = self.num_heads // self.num_kv_heads
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
            sc = sc / math.sqrt(self.config.head_dim)
            gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
            mask = gap >= 0
            if self.window is not None:
                mask &= gap < self.window
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
            return self._out(x, o.reshape(b, s, -1))

    @_scope("pt.attn")
    def paged(self, x, k_pages, v_pages, tables, starts, *,
              page_aligned: bool = False, token: bool = False):
        """THE serving hook: x [b, s, h] at absolute positions
        ``starts[b] + i``, K and V appended before any row reads (the
        packed-rows contract of ``models/llama``). ``token`` (s == 1): the
        token step, through ``append_paged_kv`` and the decode kernel;
        else the chunk form (a prefill chunk, or a verify window at
        ``pos - 1``). Either way with this kind's window."""
        from ...ops.paged_attention import (append_paged_chunk,
                                            append_paged_kv,
                                            paged_decode_attention,
                                            paged_prefill_attention)

        x = _raw(x)
        b, s, _ = x.shape
        with jax.named_scope(self.scope):
            pos = starts[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            q, k, v = self._qkv(x, pos)
            if token:
                k_pages, v_pages = append_paged_kv(
                    k_pages, v_pages, k[:, 0], v[:, 0], tables, starts)
                o = paged_decode_attention(
                    q[:, 0], k_pages, v_pages, tables, starts + 1,
                    window=self.window)
            else:
                k_pages, v_pages = append_paged_chunk(
                    k_pages, v_pages, k, v, tables, starts, page_aligned)
                o = paged_prefill_attention(q, k_pages, v_pages, tables,
                                            starts, window=self.window)
            return self._out(x, o.reshape(b, s, -1)), k_pages, v_pages


class AfmoeMLP(Layer):
    """``W_down(silu(W_gate x) * W_up x)``: a dense layer's FFN and the
    shared expert."""

    def __init__(self, config: AfmoeConfig, width: int):
        super().__init__()
        h = config.hidden_size
        init = I.Normal(std=config.initializer_range)
        mk = lambda din, dout: self.create_parameter(
            [din, dout], dtype=config.dtype, default_initializer=init)
        self.gate_proj_weight = annotate(mk(h, width), "embed", "mlp")
        self.up_proj_weight = annotate(mk(h, width), "embed", "mlp")
        self.down_proj_weight = annotate(mk(width, h), "mlp", "embed")

    def forward(self, x):
        x = _raw(x)
        act = (jax.nn.silu(jnp.matmul(x, self.gate_proj_weight._data))
               * jnp.matmul(x, self.up_proj_weight._data))
        return jnp.matmul(act, self.down_proj_weight._data)


def _moe(config: AfmoeConfig) -> DroplessMoE:
    first, count = config.experts_held
    return DroplessMoE(
        config.hidden_size, config.num_experts, config.moe_intermediate_size,
        gate=SigmoidGate(config.hidden_size, config.num_experts,
                         topk=config.num_experts_per_tok, use_bias=True,
                         renormalize=config.route_norm,
                         scaling=config.route_scale, norm_eps=1e-20,
                         initializer_range=config.initializer_range),
        first=first, count=count, dtype=config.dtype,
        initializer_range=config.initializer_range,
        shared=AfmoeMLP(config, config.moe_intermediate_size
                        * config.num_shared_experts))


class AfmoeDecoderLayer(Layer):
    def __init__(self, config: AfmoeConfig, index: int):
        super().__init__()
        self.kind = config.layer_types[index]
        h = config.hidden_size
        self.input_layernorm = _norm(config, h)
        self.self_attn = AfmoeAttention(config, self.kind)
        self.post_attention_layernorm = _norm(config, h)
        self.pre_mlp_layernorm = _norm(config, h)
        self.routed = index >= config.num_dense_layers
        self.mlp = (_moe(config) if self.routed else
                    AfmoeMLP(config, config.intermediate_size))
        self.post_mlp_layernorm = _norm(config, h)

    def ffn(self, x):
        """``x + n(FFN(n(x)))`` and the rows each held expert got (None
        for a dense layer)."""
        b = self.pre_mlp_layernorm(x)
        if self.routed:
            m, rows = self.mlp(b, with_rows=True)
        else:
            with jax.named_scope("pt.mlp"):
                m, rows = self.mlp(b), None
        return x + self.post_mlp_layernorm(m), rows


class AfmoeModel(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens_weight = annotate(self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=I.Normal(std=config.initializer_range)),
            "vocab_in", "embed")
        self.layers = LayerList([AfmoeDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = _norm(config, config.hidden_size)

    def embed(self, ids):
        x = jnp.take(self.embed_tokens_weight._data, ids, axis=0)
        if not self.config.mup_enabled:
            return x
        return (x.astype(jnp.float32)
                * math.sqrt(self.config.hidden_size)).astype(x.dtype)


class AfmoeForCausalLM(Layer):
    """``AfmoeModel`` with the untied head and the serving engine's
    hooks."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.model = AfmoeModel(config)
        self.lm_head_weight = annotate(self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=I.Normal(std=config.initializer_range)),
            "embed", "vocab")

    @_scope("pt.lm_head")
    def logits(self, hidden):
        return jnp.matmul(hidden, self.lm_head_weight._data)

    def forward(self, input_ids):
        """Logits [b, s, vocab] float32 of ``input_ids`` [b, s], no cache."""
        model = self.model
        x = model.embed(_raw(input_ids))
        for layer in model.layers:
            a = layer.self_attn(layer.input_layernorm(x))
            x, _ = layer.ffn(x + layer.post_attention_layernorm(a))
        return self.logits(model.norm(x)).astype(jnp.float32)

    # ---- serving hooks (contracts: models/llama/modeling.py) --------------
    def kv_groups(self):
        """The page groups, the full one first: ``(kind, window)``. A model
        with no sliding layer has the one."""
        kinds = set(self.config.layer_types)
        return ([("full", None)]
                + [("sliding", self.config.sliding_window)]
                * (SLIDING in kinds))

    def kv_layer_groups(self):
        """Each layer's group, by its index in ``kv_groups()``."""
        return [int(kind == SLIDING) for kind in self.config.layer_types]

    def _init_paged_caches(self, b, max_len, page_size=64, num_blocks=None,
                           kv_dtype=None, kv_shards=1, group_blocks=None):
        """A ``(k_pages, v_pages)`` pair a layer, in the form
        ``kv_pool_shape`` gives. ``group_blocks`` (the engine's, one count
        a group of ``kv_groups()``) sizes each kind's pools on their own;
        without it every layer gets ``num_blocks`` pages and the one table
        serves all (a window then only masks)."""
        from ...ops.paged_attention import kv_pool_shape, pool_pages

        cfg = self.config
        if kv_dtype not in (None, "param"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}: window "
                             f"page groups have no int8 block format")
        dtype = self.model.embed_tokens_weight._data.dtype
        maxp = -(-max_len // page_size)
        npages = b * maxp if num_blocks is None else int(num_blocks)
        if npages < b * maxp:
            raise ValueError(f"num_blocks {npages} < {b * maxp} — the pool "
                             "cannot back every slot's table")
        sizes = ([npages] * 2 if group_blocks is None
                 else [int(n) for n in group_blocks])
        kv = []
        for g in self.kv_layer_groups():
            shape = kv_pool_shape(pool_pages(sizes[g], dtype),
                                  cfg.num_key_value_heads, page_size,
                                  cfg.head_dim, dtype, shards=kv_shards)
            kv.append((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)))
        tables = jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp)
        return {"kv": kv, "tables": tables}

    def _paged(self, x, caches, starts, **how):
        """Every layer's hook over x [b, s, h] from ``starts``: (hidden
        states, caches, the rows each held expert of each expert layer
        got)."""
        from ...ops.paged_attention import group_table

        kv, tables = caches["kv"], caches["tables"]
        new_kv, rows = [], []
        for layer, g, (kp, vp) in zip(self.model.layers,
                                      self.kv_layer_groups(), kv):
            a, kp, vp = layer.self_attn.paged(
                layer.input_layernorm(x), kp, vp,
                group_table(tables, g), starts, **how)
            new_kv.append((kp, vp))
            x, r = layer.ffn(x + layer.post_attention_layernorm(a))
            if r is not None:
                rows.append(r)
        return x, {"kv": new_kv, "tables": tables}, rows

    def paged_token_step(self, toks, caches, pos_vec):
        """ONE token per row at per-row positions; returns (logits [b, vocab]
        f32, caches). Parked rows (``pos_vec == 0`` over parking-page
        tables) write the parking pages only. ``caches`` comes back with
        ``"counters"``: the rows each held expert of each expert layer got
        (``moe_rows``) and the picks made (``moe_picks``)."""
        x, out, rows = self._paged(self.model.embed(toks[:, None]), caches,
                                   pos_vec, token=True)
        logits = self.logits(self.model.norm(x)[:, -1])
        if rows:
            picks = toks.shape[0] * self.config.num_experts_per_tok
            out["counters"] = {
                "moe_rows": jnp.stack(rows),
                "moe_picks": jnp.full((len(rows),), picks, jnp.int32)}
        return logits.astype(jnp.float32), out

    def paged_prefill_chunk(self, ids, caches, starts):
        """Prefill ONE chunk per row at per-row page-aligned offsets (the
        packed-rows contract of ``models/llama``)."""
        _, out, _ = self._paged(self.model.embed(ids), caches, starts,
                                page_aligned=True)
        return out

    def paged_verify_step(self, toks, caches, pos_vec):
        """Refused: the engine serves no speculative decoding over page
        groups (a rejected draft's pages would have to come back to two
        pools). The layer hook itself would take the window at
        ``pos - 1``."""
        from ...ops.paged_attention import LayerStateError

        raise LayerStateError(
            "PT-SRV-009: AfmoeForCausalLM keeps layers of kinds 'full' and "
            "'sliding' (a page group a kind); speculative decoding is not "
            "served over page groups")
