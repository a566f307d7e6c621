"""Attention functionals (reference: python/paddle/nn/functional/flash_attention.py).

Layout follows the reference: q/k/v are [batch, seq, num_heads, head_dim]
(flash_attention.py:195). On TPU the hot path is a Pallas flash-attention kernel
(paddle_tpu/ops/flash_attention.py); elsewhere (CPU tests, odd shapes) an XLA
composite attention is used — still fused well by XLA, just not block-streamed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import flags
from ...core.op_registry import apply_fn
from ...framework.random import next_key


def _xla_attention(q, k, v, bias=None, causal=False, scale=None, dropout=0.0, dropout_key=None):
    # q,k,v: [b, s, h, d] -> compute in [b, h, s, d]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    s = scale if scale is not None else d ** -0.5
    # GQA: broadcast kv heads if fewer than q heads
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(mask, logits, jnp.float32(-1e9))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), jnp.zeros_like(probs))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def _attention_impl(q, k, v, bias, causal, scale, dropout, dropout_key):
    use_pallas = flags.get_flag("use_pallas_attention") and bias is None and dropout == 0.0
    if use_pallas:
        from ...ops.flash_attention import flash_attention_fwd

        return flash_attention_fwd(q, k, v, causal=causal, scale=scale)
    return _xla_attention(q, k, v, bias, causal, scale, dropout, dropout_key)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """Reference: nn/functional/flash_attention.py:976."""
    dk = next_key() if (dropout_p > 0.0 and training) else None
    drop = dropout_p if training else 0.0

    def fn(q, kk, vv, *mask):
        b = mask[0] if mask else None
        if b is not None and b.dtype == jnp.bool_:
            b = jnp.where(b, 0.0, -1e9).astype(jnp.float32)
        return _attention_impl(q, kk, vv, b, is_causal, None, drop, dk)

    args = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
    return apply_fn("scaled_dot_product_attention", fn, *args)


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    """Reference: nn/functional/flash_attention.py:195. Returns (out, softmax|None)."""
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal, training)
    return out, None


def flashmask_attention(query, key, value, startend_row_indices=None, dropout=0.0,
                        causal=False, window_size=None, return_softmax_lse=False,
                        return_seed_offset=False, fixed_seed_offset=None, rng_name="",
                        training=True, name=None):
    """Sparse-mask attention (reference :1098 over the flashmask CUDA
    kernels). The LT start/end encodings ([b, hm, kv_len, {1,2}]) stream
    through the in-repo Pallas flash kernel as per-column row bounds
    (ops/flash_attention.flash_attention_rowmask — fwd AND bwd); the 4-index
    bidirectional encodings fall back to a dense additive bias."""
    from ...core.tensor import Tensor, unwrap

    if startend_row_indices is not None:
        idx = unwrap(startend_row_indices)  # [b, hm, kv_len, {1,2,4}]
        b, hm, kv_len, nidx = idx.shape
        q_len = query.shape[1]
        if causal and nidx <= 2 and dropout == 0.0:
            # kernel path (causal LT encodings): per kv column, q rows in
            # [LT_start, LT_end) are masked (LT_end = ∞ for the 1-index form)
            start = idx[..., 0]
            end = (idx[..., 1] if nidx >= 2
                   else jnp.full_like(start, q_len + kv_len))
            from ...core.op_registry import apply_fn
            from ...ops.flash_attention import flash_attention_rowmask

            def fn(q, k, v, st, en):
                return flash_attention_rowmask(q, k, v, st, en, causal, None)

            return apply_fn("flashmask_attention", fn, query, key, value,
                            Tensor(start), Tensor(end))
        # dense additive-bias path:
        #   causal 4-index  [LTS, LTE, UTS, UTE]: two masked bands
        #   non-causal 2-index [LTS, UTE]: masked rows >= LTS OR rows < UTE
        #   non-causal 4-index [LTS, LTE, UTS, UTE]: two masked bands
        rows = jnp.arange(q_len)[None, None, :, None]
        lts = idx[..., 0][:, :, None, :]
        if causal:
            mask = rows >= lts
            if nidx >= 2:
                lte = idx[..., 1][:, :, None, :]
                mask = mask & (rows < lte)
        elif nidx == 2:
            ute = idx[..., 1][:, :, None, :]
            mask = (rows >= lts) | (rows < ute)
        else:
            lte = idx[..., 1][:, :, None, :]
            uts = idx[..., 2][:, :, None, :]
            ute = idx[..., 3][:, :, None, :]
            mask = ((rows >= lts) & (rows < lte)) | \
                   ((rows >= uts) & (rows < ute))
        bias = jnp.where(mask, jnp.float32(-1e9), 0.0)
        return scaled_dot_product_attention(query, key, value, Tensor(bias),
                                            dropout, causal, training)
    return scaled_dot_product_attention(query, key, value, None, dropout,
                                        causal, training)


def sdp_kernel(*args, **kwargs):
    import contextlib

    return contextlib.nullcontext()
