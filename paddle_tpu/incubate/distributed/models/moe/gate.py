"""MoE gates — naive top-k, GShard top-2, Switch top-1.

Parity anchor: /root/reference/python/paddle/incubate/distributed/models/moe/gate/
(base_gate.py:25 BaseGate, naive_gate.py:28 NaiveGate, gshard_gate.py:31 GShardGate,
switch_gate.py:31 SwitchGate).

TPU-native: gates here return dense dispatch/combine tensors (GShard einsum
formulation) instead of the reference's index/position buffers — index_select/
scatter dispatch is a dynamic-shape pattern XLA can't tile; the dense one-hot
formulation keeps every shape static and lets GSPMD turn the dispatch einsum
into cross-device dispatch collectives over the ``ep`` mesh axis (this
XLA version picks all-reduce of per-expert partials — see docs/MOE_AB.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .....core.tensor import Tensor
from .....nn import initializer as I
from .....nn.layer.layers import Layer


def _raw(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


class BaseGate(Layer):
    """Reference base_gate.py:25 — holds the aux (load-balance) loss."""

    def __init__(self, num_expert, world_size=1):
        super().__init__()
        self.world_size = world_size
        self.num_expert = num_expert
        self.tot_expert = world_size * num_expert
        self.loss = None

    def forward(self, x):
        raise NotImplementedError("Base gate cannot be directly used for fwd")

    def set_loss(self, loss):
        self.loss = loss

    def get_loss(self, clear=True):
        loss = self.loss
        if clear:
            self.loss = None
        return loss


def _load_balance_loss(probs, first_choice_mask):
    """GShard aux loss: E * sum_e mean_tokens(prob_e) * mean_tokens(routed_e)."""
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(first_choice_mask.astype(probs.dtype), axis=0)
    return probs.shape[-1] * jnp.sum(me * ce)


def topk_routing(probs, k: int, capacity: int, renormalize: bool = True):
    """Sparse top-k routing with per-expert capacity — ONE source of truth
    for the GShard semantics (topk_dispatch assembles its dense one-hots
    from this, routed_ffn's scatter path consumes it directly).

    probs: [tokens, E]. Returns (expert_idx [n, k] int32, cap_pos [n, k]
    int32, weight [n, k], keep [n, k] bool, aux_loss). Tokens overflowing an
    expert's capacity get keep=False and weight 0 for that choice.
    """
    n, e = probs.shape
    remaining = probs
    prev_count = jnp.zeros((e,), jnp.int32)
    gate_sum = jnp.zeros((n,), probs.dtype)
    first_mask = None
    eidxs, cposs, gates, keeps = [], [], [], []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                    # [n]
        mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)        # [n, e]
        if first_mask is None:
            first_mask = mask
        pos = jnp.cumsum(mask, axis=0) - 1 + prev_count[None, :].astype(probs.dtype)
        prev_count = prev_count + jnp.sum(mask, axis=0).astype(jnp.int32)
        within = (pos < capacity).astype(probs.dtype)
        mask = mask * within
        gate_j = jnp.sum(probs * mask, axis=-1)                 # [n]
        gate_sum = gate_sum + gate_j
        pos_tok = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)  # [n]
        eidxs.append(idx.astype(jnp.int32))
        cposs.append(pos_tok)
        gates.append(gate_j)
        keeps.append(jnp.sum(mask, axis=-1) > 0)
        remaining = remaining * (1.0 - jax.nn.one_hot(idx, e, dtype=probs.dtype))
    w = jnp.stack(gates, axis=1)                                # [n, k]
    if renormalize and k > 1:
        w = w / jnp.maximum(gate_sum, 1e-9)[:, None]
    aux = _load_balance_loss(probs, first_mask)
    return (jnp.stack(eidxs, axis=1), jnp.stack(cposs, axis=1), w,
            jnp.stack(keeps, axis=1), aux)


def topk_dispatch(probs, k: int, capacity: int, renormalize: bool = True):
    """Dense top-k routing with per-expert capacity.

    probs: [tokens, E] softmax gate probabilities.
    Returns (combine [tokens, E, C], dispatch_mask [tokens, E, C] bool, aux_loss).
    Tokens overflowing an expert's capacity are dropped for that choice
    (GShard semantics). Dense assembly over :func:`topk_routing`.
    """
    n, e = probs.shape
    eidx, cpos, w, keep, aux = topk_routing(probs, k, capacity, renormalize)
    onehot_e = jax.nn.one_hot(eidx, e, dtype=probs.dtype)       # [n, k, E]
    onehot_c = jax.nn.one_hot(cpos, capacity, dtype=probs.dtype)  # [n, k, C]
    wk = w * keep.astype(probs.dtype)
    combine = jnp.einsum("nk,nke,nkc->nec", wk, onehot_e, onehot_c)
    dispatch = combine > 0
    return combine, dispatch, aux


class NaiveGate(BaseGate):
    """Reference naive_gate.py:28 — linear scorer + top-k, no aux loss."""

    renormalize = True   # renormalize combine weights over the selected top-k
    use_aux = False      # whether the load-balance aux loss trains the gate

    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__(num_expert, world_size)
        self.top_k = topk
        self.gate_weight = self.create_parameter(
            [d_model, self.tot_expert], dtype="float32",
            default_initializer=I.XavierUniform())

    def probs(self, inp):
        logits = jnp.matmul(_raw(inp).astype(jnp.float32), self.gate_weight._data)
        return jax.nn.softmax(logits, axis=-1)

    scores = probs

    def forward(self, inp, capacity: int):
        p = self.probs(inp)
        combine, dispatch, aux = topk_dispatch(p, self.top_k, capacity,
                                               self.renormalize)
        self.set_loss(aux if self.use_aux else jnp.zeros((), jnp.float32))
        return combine, dispatch


class GShardGate(NaiveGate):
    """Reference gshard_gate.py:31 — top-2 with capacity + load-balance aux loss."""

    use_aux = True

    def __init__(self, d_model, num_expert, world_size=1, topk=2,
                 capacity=(1.2, 2.4), random_routing=True, group=None):
        if topk != 2:
            raise ValueError("topk should be 2 in gshard")
        super().__init__(d_model, num_expert, world_size, topk=2)
        self.capacity_factor = capacity[0] if isinstance(capacity, (tuple, list)) else capacity


class SwitchGate(NaiveGate):
    """Reference switch_gate.py:31 — top-1 with capacity + aux loss."""

    renormalize = False
    use_aux = True

    def __init__(self, d_model, num_expert, world_size=1, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4), group=None):
        if topk != 1:
            raise ValueError("topk should be 1 in switch")
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = switch_eps

    def probs(self, inp):
        x = _raw(inp).astype(jnp.float32)
        logits = jnp.matmul(x, self.gate_weight._data)
        if self.training and self.switch_eps > 0:
            # reference switch_gate.py: multiplicative jitter noise in training
            from .....framework.random import next_key

            noise = jax.random.uniform(
                next_key(), logits.shape, jnp.float32,
                1.0 - self.switch_eps, 1.0 + self.switch_eps)
            logits = logits * noise
        return jax.nn.softmax(logits, axis=-1)


class SigmoidGate(BaseGate):
    """Sigmoid scores, bias-corrected choice, gates renormalised over the
    chosen (the DeepSeek-V3 / LFM2 router). ``s = sigmoid(x . W)``; the
    ``topk`` experts chosen are the largest of ``s + b`` (``b`` the expert
    bias: it corrects the load and takes part in the CHOICE only); the
    gates are ``s`` at the chosen, divided by their sum + ``norm_eps`` when
    ``renormalize``, times ``scaling``. No capacity, no aux loss: the bias
    is what balances. The matmul, the sigmoid and the top-k run in float32
    whatever the model's type: a choice that flips on a bf16 rounding sends
    a token through other experts.

    ``route(x) -> (expert_idx [n, k] int32, gates [n, k] f32)`` is what a
    dropless expert layer takes (``DroplessMoE``)."""

    use_aux = False

    def __init__(self, d_model, num_expert, topk=2, use_bias=True,
                 renormalize=True, scaling=1.0, norm_eps=1e-6,
                 initializer_range=0.02):
        super().__init__(num_expert, 1)
        self.top_k = int(topk)
        self.renormalize = bool(renormalize)
        self.scaling = float(scaling)
        self.norm_eps = float(norm_eps)
        self.gate_weight = self.create_parameter(
            [d_model, num_expert], dtype="float32",
            default_initializer=I.Normal(std=initializer_range))
        self.expert_bias = (self.create_parameter(
            [num_expert], dtype="float32", is_bias=True)
            if use_bias else None)

    def scores(self, inp):
        x = _raw(inp).astype(jnp.float32)
        w = self.gate_weight._data.astype(jnp.float32)
        return jax.nn.sigmoid(jnp.matmul(
            x, w, precision=jax.lax.Precision.HIGHEST))

    def route(self, inp):
        s = self.scores(inp)
        pick = s if self.expert_bias is None else (
            s + self.expert_bias._data.astype(jnp.float32))
        _, idx = jax.lax.top_k(pick, self.top_k)
        g = jnp.take_along_axis(s, idx, axis=-1)
        if self.renormalize:
            g = g / (g.sum(-1, keepdims=True) + self.norm_eps)
        return idx.astype(jnp.int32), g * self.scaling

    def forward(self, inp):
        return self.route(inp)
