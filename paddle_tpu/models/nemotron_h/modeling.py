"""Nemotron-H family (published config: ``model_type`` ``nemotron_h``): blocks
of ONE mixer each behind one RMSNorm, ``h = h + mixer(RMSNorm(h))``, the
mixer's kind a letter of ``hybrid_override_pattern``; after the last block
an RMSNorm and an untied head. No positional embedding anywhere.

  M  Mamba-2. ``[z | xBC | dt] = u W_in``; ``xBC`` through a depthwise
     causal convolution of ``conv_kernel`` taps with bias, then silu; split
     ``x`` [H, P], ``B``, ``C`` [G, N]; ``dt = softplus(dt + dt_bias)`` (no
     clamp), ``A = -exp(A_log)``; the recurrence of ``ops/ssd.py``
     (``S_t = exp(dt A) S_{t-1} + dt x (x) B``, ``y = S C + D x``, the state
     float32); ``y * silu(z)`` through an RMSNorm over groups of
     ``d_inner / G`` (gate first, then norm); ``W_out``. ``d_inner`` is
     ``mamba_num_heads * mamba_head_dim`` (the family's code never reads
     ``expand``).
  *  attention. q (heads x head_dim), k, v (kv heads x head_dim) without
     bias, rotary or QK-norm; causal softmax of ``q k^T / sqrt(head_dim)``;
     ``W_o``.
  E  experts. ``DroplessMoE`` behind a ``SigmoidGate`` (router in float32,
     the correction bias in the choice only, gates renormalised over the
     chosen with ``+ 1e-20``, times ``routed_scaling_factor``) over
     ``Relu2ExpertFFN`` experts (``relu(x W_up)^2 W_down``), plus one shared
     expert of the same form on every token. The layer routes over
     ``n_routed_experts`` and holds ``experts_held`` of them,
     ``[first, first + count)``: a chip's share of an expert-parallel layer,
     its output the held experts' gated sum plus the shared expert, whole.

Serving. ``paged_token_step`` and ``paged_prefill_chunk`` keep
``models/llama``'s contracts; ``paged_verify_step`` raises
``LayerStateError``. ``caches["kv"]`` has an entry for each layer that keeps
something, in depth order: ``*`` a ``(k_pages, v_pages)`` pair, ``M`` an
``ops.paged_attention.SeqState`` (the recurrence's matrix and the
convolution's window, kept a SLOT: docs/SERVING.md "State that is not
pages"); ``E`` keeps nothing and has none.
The engine says which slot a row is: ``caches["seq_live"]`` [b] bool in the
decode block (row ``i`` is slot ``i``; False: the row does not decode and
its state stays), ``caches["seq_slots"]`` [b] in the first-token program
(out of range: a dummy row), ``caches["seq"] = (slots, count)`` in the
packed chunk (``count``: the row's positions whose state is kept, which
leaves out the prompt's last token, since the first-token program steps it
again). ``paged_token_step`` returns, in ``caches["counters"]``, the rows
each held expert of each expert layer got (``moe_rows``) and the picks made
(``moe_picks`` [expert layers]: rows x experts a token).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.tensor import Tensor
from ...distributed.auto_parallel.logical_sharding import annotate
from ...incubate.distributed.models.moe import (DroplessMoE, Relu2ExpertFFN,
                                                SigmoidGate)
from ...nn import initializer as I
from ...nn.layer.layers import Layer, LayerList
from ...ops import ssd

MAMBA, ATTN, MOE = "M", "*", "E"


def _scope(name):
    return functools.partial(jax.named_call, name=name)


def _raw(x):
    return x._data if isinstance(x, Tensor) else x


class NemotronHConfig:
    """The published keys of ``nemotron_h`` that shape the model, plus
    ``experts_held`` (``(first, count)`` of the routed experts this build
    holds; None: all), ``dtype`` and ``initializer_range``."""

    def __init__(self, vocab_size: int = 131072, hidden_size: int = 2688,
                 hybrid_override_pattern: str =
                 "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
                 num_attention_heads: int = 32, num_key_value_heads: int = 2,
                 head_dim: int = 128, mamba_num_heads: int = 64,
                 mamba_head_dim: int = 64, n_groups: int = 8,
                 ssm_state_size: int = 128, conv_kernel: int = 4,
                 chunk_size: int = 128, n_routed_experts: int = 128,
                 experts_held: Optional[Tuple[int, int]] = None,
                 num_experts_per_tok: int = 6,
                 moe_intermediate_size: int = 1856,
                 moe_shared_expert_intermediate_size: int = 3712,
                 norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 2.5,
                 time_step_min: float = 0.001, time_step_max: float = 0.1,
                 norm_eps: float = 1e-5,
                 max_position_embeddings: int = 262144,
                 initializer_range: float = 0.02, dtype: str = "bfloat16"):
        pattern = str(hybrid_override_pattern)
        if not pattern or set(pattern) - {MAMBA, ATTN, MOE}:
            raise ValueError(f"hybrid_override_pattern holds letters other "
                             f"than {MAMBA} {ATTN} {MOE}: {pattern!r}")
        if mamba_num_heads % n_groups or num_attention_heads % \
                num_key_value_heads:
            raise ValueError("heads must divide into their groups")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.hybrid_override_pattern = pattern
        self.num_hidden_layers = len(pattern)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.n_routed_experts = n_routed_experts
        self.experts_held = ((0, n_routed_experts) if experts_held is None
                             else tuple(int(v) for v in experts_held))
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.norm_eps = norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.dtype = dtype

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @classmethod
    def tiny(cls, **over):
        kw = dict(vocab_size=512, hidden_size=64,
                  hybrid_override_pattern="MEM*EME", num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
                  mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                  chunk_size=8, n_routed_experts=8, num_experts_per_tok=3,
                  moe_intermediate_size=32,
                  moe_shared_expert_intermediate_size=48,
                  max_position_embeddings=256, initializer_range=0.1,
                  dtype="float32")
        kw.update(over)
        return cls(**kw)


def _rms(x, weight, eps, groups: int = 1):
    """RMSNorm over the last axis, or over each of ``groups`` equal parts of
    it, in float32; the gain in the model's type."""
    xf = x.astype(jnp.float32)
    parts = xf.reshape(*xf.shape[:-1], groups, -1)
    var = jnp.mean(parts * parts, axis=-1, keepdims=True)
    xf = (parts * jax.lax.rsqrt(var + eps)).reshape(xf.shape)
    return xf.astype(x.dtype) * weight


class NemotronHRMSNorm(Layer):
    def __init__(self, width: int, eps: float, dtype: str):
        super().__init__()
        self.eps = eps
        self.weight = annotate(self.create_parameter(
            [width], dtype=dtype, default_initializer=I.Constant(1.0)),
            "norm")

    def forward(self, x):
        return _rms(_raw(x), self.weight._data, self.eps)


class NemotronHMamba2(Layer):
    """The Mamba-2 mixer. Served, its state is a ``SeqState`` row a slot:
    the matrix ``S`` and the last ``conv_kernel - 1`` inputs of the
    convolution."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        cfg = self.config = config
        h, d, heads = cfg.hidden_size, cfg.d_inner, cfg.mamba_num_heads
        init = I.Normal(std=cfg.initializer_range)
        mk = lambda shape, dtype=cfg.dtype, init=init: self.create_parameter(
            shape, dtype=dtype, default_initializer=init)
        self.in_proj_weight = annotate(
            mk([h, d + cfg.conv_width + heads]), "embed", "mlp")
        self.conv_weight = mk([cfg.conv_width, cfg.conv_kernel])
        self.conv_bias = mk([cfg.conv_width], init=I.Constant(0.0))
        # the family's initialisation, spread evenly in place of drawn:
        # A in [1, 16], dt in [time_step_min, time_step_max] (log scale)
        dt = np.exp(np.linspace(math.log(cfg.time_step_min),
                                math.log(cfg.time_step_max), heads))
        self.dt_bias = mk([heads], "float32", I.Assign(
            (dt + np.log(-np.expm1(-dt))).astype(np.float32)))
        self.A_log = mk([heads], "float32", I.Assign(
            np.log(np.linspace(1.0, 16.0, heads)).astype(np.float32)))
        self.D = mk([heads], "float32", I.Constant(1.0))
        self.norm_weight = mk([d], init=I.Constant(1.0))
        self.out_proj_weight = annotate(mk([d, h]), "mlp", "embed")

    def _project(self, u):
        cfg = self.config
        with jax.named_scope("pt.ssm.in_proj"):
            zxd = jnp.matmul(u, self.in_proj_weight._data)
        z, xbc, dt = jnp.split(zxd, [cfg.d_inner,
                                     cfg.d_inner + cfg.conv_width], axis=-1)
        return z, xbc, dt

    def _conv(self, xbc, prev):
        """silu(bias + sum_k w[:, k] * in_{t-(K-1)+k}) on xbc [b, s, W]
        after ``prev`` [b, K-1, W] (oldest first)."""
        with jax.named_scope("pt.ssm.conv"):
            s, taps = xbc.shape[1], self.config.conv_kernel
            full = jnp.concatenate([prev.astype(xbc.dtype), xbc], axis=1)
            w = self.conv_weight._data
            out = sum(w[:, k] * full[:, k:k + s] for k in range(taps))
            return jax.nn.silu(out + self.conv_bias._data)

    def _ssm_inputs(self, xbc, dt):
        """(x [.., H, P], B, C [.., G, N], dt [.., H] float32) of the
        convolved ``xbc`` [.., W] and the raw ``dt`` [.., H]."""
        cfg = self.config
        g, n = cfg.n_groups, cfg.ssm_state_size
        x, b_, c_ = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + g * n],
                              axis=-1)
        lead = xbc.shape[:-1]
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias._data)
        return (x.reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim),
                b_.reshape(*lead, g, n), c_.reshape(*lead, g, n), dt)

    def _out(self, y, z):
        cfg = self.config
        y = y.reshape(*z.shape)
        with jax.named_scope("pt.ssm.norm"):
            y = _rms(y * jax.nn.silu(z), self.norm_weight._data,
                     cfg.norm_eps, groups=cfg.n_groups)
        with jax.named_scope("pt.ssm.out_proj"):
            return jnp.matmul(y, self.out_proj_weight._data)

    def _A(self):
        return -jnp.exp(self.A_log._data.astype(jnp.float32))

    @_scope("pt.ssm")
    def forward(self, u):
        """u [b, s, hidden], every row a sequence from its start."""
        u = _raw(u)
        cfg = self.config
        z, xbc, dt = self._project(u)
        prev = jnp.zeros((u.shape[0], cfg.conv_kernel - 1, cfg.conv_width),
                         xbc.dtype)
        x, b_, c_, dt = self._ssm_inputs(self._conv(xbc, prev), dt)
        y, _ = ssd.ssd_scan(x, dt, self._A(), b_, c_, self.D._data,
                            chunk=cfg.chunk_size)
        return self._out(y, z)

    @_scope("pt.ssm")
    def paged_chunk(self, u, state, slots, starts, count):
        """u [b, s, hidden] at positions ``starts[b] + i`` of the sequences
        in ``slots``; the first ``count[b]`` positions' state is kept. The
        rows of one sequence are adjacent and rising, as the engine orders
        a pack (``ssd_scan_pooled``): a row resumes from what the row
        before it left, in this very program, and a slot's matrix state is
        read once and written back once a run of its rows."""
        from ...ops.paged_attention import SeqState

        u = _raw(u)
        cfg = self.config
        b, s, _ = u.shape
        keep = cfg.conv_kernel - 1
        z, xbc, dt = self._project(u)
        fresh = starts == 0
        slot_c = jnp.clip(slots, 0, state.conv.shape[0] - 1)
        # the window a row leaves: its last ``keep`` kept inputs, or, of a
        # row with fewer, what was there before shifted on
        at = count[:, None] - keep + jnp.arange(keep, dtype=jnp.int32)
        tail = jnp.take_along_axis(xbc, jnp.maximum(at, 0)[..., None], axis=1)

        def window(pool, inp):
            slot, is_fresh, n, at_r, tail_r = inp
            old = jax.lax.dynamic_index_in_dim(pool, slot, 0, keepdims=False)
            prev = jnp.where(is_fresh, jnp.zeros((), old.dtype), old)
            shifted = jnp.take(prev, jnp.clip(at_r + keep, 0, keep - 1),
                               axis=0)
            new = jnp.where((at_r >= 0)[:, None], tail_r.astype(old.dtype),
                            shifted)
            new = jnp.where(n > 0, new, old)
            return (jax.lax.dynamic_update_index_in_dim(pool, new, slot, 0),
                    prev)

        with jax.named_scope("pt.ssm.conv"):
            conv, prev = jax.lax.scan(
                window, state.conv, (slot_c, fresh, count, at, tail))
        x, b_, c_, dt = self._ssm_inputs(self._conv(xbc, prev), dt)
        dt = jnp.where(jnp.arange(s)[None, :, None] < count[:, None, None],
                       dt, 0.0)
        y, pool = ssd.ssd_scan_pooled(
            state.ssm, x, dt, self._A(), b_, c_, self.D._data, slots, fresh,
            count, chunk=cfg.chunk_size)
        return self._out(y, z), SeqState(pool, conv)

    @_scope("pt.ssm")
    def paged_token(self, u, state, pos_vec, slots=None, live=None):
        """One token a row (u [b, 1, hidden]) at ``pos_vec``; which slot a
        row is, and whether it decodes: ``ops.ssd.ssd_step``."""
        from ...ops.paged_attention import SeqState

        u = _raw(u)
        z, xbc, dt = self._project(u)
        fresh = pos_vec == 0
        with jax.named_scope("pt.ssm.conv"):
            old = (state.conv if slots is None else
                   state.conv[jnp.clip(slots, 0, state.conv.shape[0] - 1)])
            prev = jnp.where(fresh[:, None, None], jnp.zeros((), old.dtype),
                             old)
            new = jnp.concatenate([prev[:, 1:], xbc.astype(old.dtype)],
                                  axis=1)
            conv = (jnp.where(live[:, None, None], new, old) if slots is None
                    else state.conv.at[slots].set(new, mode="drop"))
        x, b_, c_, dt = self._ssm_inputs(self._conv(xbc, prev), dt)
        y, pool = ssd.ssd_step(state.ssm, x[:, 0], dt[:, 0], self._A(),
                               b_[:, 0], c_[:, 0], self.D._data, fresh,
                               slots=slots, live=live)
        return self._out(y[:, None], z), SeqState(pool, conv)


class NemotronHAttention(Layer):
    """Grouped-query attention without bias, rotary or QK-norm."""

    def __init__(self, config: NemotronHConfig):
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

    def _qkv(self, x):
        b, s, _ = x.shape
        hd = self.config.head_dim
        return tuple(jnp.matmul(x, w._data).reshape(b, s, -1, hd) for w in
                     (self.q_proj_weight, self.k_proj_weight,
                      self.v_proj_weight))

    @_scope("pt.attn")
    def forward(self, x):
        x = _raw(x)
        b, s, _ = x.shape
        q, k, v = self._qkv(x)
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
    def paged_chunk(self, x, k_pages, v_pages, tables, starts):
        from ...ops.paged_attention import (append_paged_chunk,
                                            paged_prefill_attention)

        x = _raw(x)
        b, s, _ = x.shape
        q, k, v = self._qkv(x)
        k_pages, v_pages = append_paged_chunk(
            k_pages, v_pages, k, v, tables, starts, True)
        out = paged_prefill_attention(q, k_pages, v_pages, tables, starts)
        return (jnp.matmul(out.reshape(b, s, -1), self.o_proj_weight._data),
                k_pages, v_pages)

    @_scope("pt.attn")
    def paged_token(self, x, k_pages, v_pages, tables, pos_vec):
        from ...ops.paged_attention import (append_paged_kv,
                                            paged_decode_attention)

        x = _raw(x)
        b = x.shape[0]
        q, k, v = self._qkv(x)
        k_pages, v_pages = append_paged_kv(
            k_pages, v_pages, k[:, 0], v[:, 0], tables, pos_vec)
        out = paged_decode_attention(q[:, 0], k_pages, v_pages, tables,
                                     pos_vec + 1)
        return (jnp.matmul(out.reshape(b, 1, -1), self.o_proj_weight._data),
                k_pages, v_pages)


class NemotronHMLP(Layer):
    """``relu(x W_up)^2 W_down``: the shared expert."""

    def __init__(self, config: NemotronHConfig, width: int):
        super().__init__()
        init = I.Normal(std=config.initializer_range)
        mk = lambda din, dout: self.create_parameter(
            [din, dout], dtype=config.dtype, default_initializer=init)
        self.up_proj_weight = annotate(mk(config.hidden_size, width),
                                       "embed", "mlp")
        self.down_proj_weight = annotate(mk(width, config.hidden_size),
                                         "mlp", "embed")

    def forward(self, x):
        up = jnp.matmul(_raw(x), self.up_proj_weight._data)
        return jnp.matmul(jnp.square(jax.nn.relu(up)),
                          self.down_proj_weight._data)


def _moe(config: NemotronHConfig) -> DroplessMoE:
    first, count = config.experts_held
    kw = dict(dtype=config.dtype, initializer_range=config.initializer_range)
    return DroplessMoE(
        config.hidden_size, config.n_routed_experts,
        config.moe_intermediate_size,
        gate=SigmoidGate(config.hidden_size, config.n_routed_experts,
                         topk=config.num_experts_per_tok, use_bias=True,
                         renormalize=config.norm_topk_prob,
                         scaling=config.routed_scaling_factor,
                         norm_eps=1e-20,
                         initializer_range=config.initializer_range),
        first=first, count=count,
        experts=Relu2ExpertFFN(count, config.hidden_size,
                               config.moe_intermediate_size, **kw),
        shared=NemotronHMLP(config,
                            config.moe_shared_expert_intermediate_size), **kw)


class NemotronHBlock(Layer):
    def __init__(self, config: NemotronHConfig, index: int):
        super().__init__()
        self.kind = config.hybrid_override_pattern[index]
        self.norm = NemotronHRMSNorm(config.hidden_size, config.norm_eps,
                                     config.dtype)
        self.mixer = (NemotronHMamba2(config) if self.kind == MAMBA else
                      NemotronHAttention(config) if self.kind == ATTN else
                      _moe(config))


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embed_tokens_weight = annotate(self.create_parameter(
            [config.vocab_size, config.hidden_size], dtype=config.dtype,
            default_initializer=I.Normal(std=config.initializer_range)),
            "vocab_in", "embed")
        self.layers = LayerList([NemotronHBlock(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm_f = NemotronHRMSNorm(config.hidden_size, config.norm_eps,
                                       config.dtype)


class NemotronHForCausalLM(Layer):
    """``NemotronHModel`` with the untied head and the serving engine's
    hooks."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.model = NemotronHModel(config)
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
        x = jnp.take(model.embed_tokens_weight._data, _raw(input_ids), axis=0)
        for layer in model.layers:
            x = x + layer.mixer(layer.norm(x))
        return self.logits(model.norm_f(x)).astype(jnp.float32)

    # ---- serving hooks (contracts: models/llama/modeling.py) --------------
    def _init_paged_caches(self, b, max_len, page_size=64, num_blocks=None,
                           kv_dtype=None, kv_shards=1):
        """What each layer that keeps something keeps, in depth order: ``*``
        a ``(k_pages, v_pages)`` pair in the form ``kv_pool_shape`` gives,
        ``M`` a ``SeqState`` of ``b`` rows (one a slot)."""
        from ...ops.paged_attention import (SeqState, kv_pool_shape,
                                            pool_pages)

        cfg = self.config
        if kv_dtype not in (None, "param"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r}: a state "
                             f"kept a sequence has no int8 block format")
        dtype = self.model.embed_tokens_weight._data.dtype
        maxp = -(-max_len // page_size)
        npages = b * maxp if num_blocks is None else int(num_blocks)
        if npages < b * maxp:
            raise ValueError(f"num_blocks {npages} < {b * maxp} — the pool "
                             "cannot back every slot's table")
        npages = pool_pages(npages, dtype)
        kv = []
        for kind in cfg.hybrid_override_pattern:
            if kind == MAMBA:
                kv.append(SeqState(
                    jnp.zeros((b, cfg.mamba_num_heads, cfg.mamba_head_dim,
                               cfg.ssm_state_size), jnp.float32),
                    jnp.zeros((b, cfg.conv_kernel - 1, cfg.conv_width),
                              dtype)))
            elif kind == ATTN:
                shape = kv_pool_shape(npages, cfg.num_key_value_heads,
                                      page_size, cfg.head_dim, dtype,
                                      shards=kv_shards)
                kv.append((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)))
        tables = jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp)
        return {"kv": kv, "tables": tables}

    def paged_token_step(self, toks, caches, pos_vec):
        """ONE token per row at per-row positions; returns (logits [b, vocab]
        f32, caches). A row that does not decode (``seq_live`` False, or a
        ``seq_slots`` entry out of range) writes the parking page's K and V
        and no state."""
        model = self.model
        kv, tables = caches["kv"], caches["tables"]
        slots, live = caches.get("seq_slots"), caches.get("seq_live")
        if slots is None and live is None:
            live = jnp.ones(toks.shape, bool)
        x = jnp.take(model.embed_tokens_weight._data, toks[:, None], axis=0)
        kept, new_kv, rows = iter(kv), [], []
        for layer in model.layers:
            u = layer.norm(x)
            if layer.kind == MAMBA:
                a, entry = layer.mixer.paged_token(u, next(kept), pos_vec,
                                                   slots=slots, live=live)
                new_kv.append(entry)
            elif layer.kind == ATTN:
                a, kp, vp = layer.mixer.paged_token(u, *next(kept), tables,
                                                    pos_vec)
                new_kv.append((kp, vp))
            else:
                a, r = layer.mixer(u, with_rows=True)
                rows.append(r)
            x = x + a
        logits = self.logits(model.norm_f(x)[:, -1])
        out = {"kv": new_kv, "tables": tables}
        if rows:
            picks = toks.shape[0] * self.config.num_experts_per_tok
            out["counters"] = {
                "moe_rows": jnp.stack(rows),
                "moe_picks": jnp.full((len(rows),), picks, jnp.int32)}
        return logits.astype(jnp.float32), out

    def paged_prefill_chunk(self, ids, caches, starts):
        """Prefill ONE chunk per row at per-row page-aligned offsets (the
        packed-rows contract of ``models/llama``). ``caches["seq"]`` =
        ``(slots, count)`` [b] int32 each: the slot whose state a row
        continues and the row's positions whose state is kept (without
        them: row ``i`` is slot ``i`` and every position counts)."""
        model = self.model
        kv, tables = caches["kv"], caches["tables"]
        b, s = ids.shape
        slots, count = caches.get("seq") or (
            jnp.arange(b, dtype=jnp.int32), jnp.full((b,), s, jnp.int32))
        x = jnp.take(model.embed_tokens_weight._data, ids, axis=0)
        kept, new_kv = iter(kv), []
        for layer in model.layers:
            u = layer.norm(x)
            if layer.kind == MAMBA:
                a, entry = layer.mixer.paged_chunk(u, next(kept), slots,
                                                   starts, count)
                new_kv.append(entry)
            elif layer.kind == ATTN:
                a, kp, vp = layer.mixer.paged_chunk(u, *next(kept), tables,
                                                    starts)
                new_kv.append((kp, vp))
            else:
                a = layer.mixer(u)
            x = x + a
        return {"kv": new_kv, "tables": tables}

    def paged_verify_step(self, toks, caches, pos_vec):
        """Refused: a recurrent state cannot take a rejected draft back, so
        a verify window over it would lose state silently. The engine
        refuses speculative decoding over such layers with the same
        error."""
        from ...ops.paged_attention import LayerStateError

        raise LayerStateError(
            "PT-SRV-009: NemotronHForCausalLM keeps layers of kind 'seq' "
            "(SeqState, a recurrent state kept a slot); a verify window "
            "cannot take a rejected draft back out of it")
