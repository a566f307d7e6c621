"""Mixture-of-Experts layer with expert parallelism over the ``ep`` mesh axis.

Parity anchor: /root/reference/python/paddle/incubate/distributed/models/moe/
moe_layer.py:263 ``MoELayer`` (gates gshard/switch/naive, alltoall dispatch via
``global_scatter``/``global_gather`` utils.py:32, MoE grad clip).

TPU-native redesign: the reference scatters tokens with index_select + NCCL
alltoall (dynamic shapes). Here routing is the GShard dense-einsum formulation —
dispatch/combine one-hot tensors with a static per-expert ``capacity`` — and the
expert FFN is ONE batched computation over stacked weights ``[E, ...]`` sharded
over the ``ep`` mesh axis ("expert" logical axis). When tokens are sharded over
dp/fsdp and experts over ep, GSPMD lowers the dispatch einsum to cross-device
dispatch collectives riding ICI (measured: all-reduce of per-expert partials —
the role of the reference's hand-issued alltoall; docs/MOE_AB.md).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .....core.tensor import Tensor
from .....distributed.auto_parallel.logical_sharding import annotate, constrain
from .....nn import initializer as I
from .....nn.layer.layers import Layer
from .gate import BaseGate, GShardGate, NaiveGate, SigmoidGate, SwitchGate


def _raw(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


def routed_ffn(tokens, probs, expert_fn, k: int, capacity: int,
               renormalize: bool = True, dispatch_mode: str = "auto"):
    """Shared dispatch → expert_fn → combine pipeline on raw arrays.

    tokens: [n, d]; probs: [n, E]; expert_fn: [E, C, d] -> [E, C, d'].
    Returns (out [n, d'], aux_loss). Used by MoELayer and fused_moe so the
    routing/capacity semantics exist exactly once.

    dispatch_mode:
      - "einsum": GShard dense one-hot dispatch/combine — O(n*E*C*d) MXU
        work; GSPMD inserts the ep dispatch collectives when tokens are
        dp-sharded and experts ep-sharded (docs/MOE_AB.md). Fine for few
        experts.
      - "scatter": sparse dispatch via segment-sum scatter + gather —
        O(n*k*d), the sorted/ragged-dispatch regime for MANY experts
        (VERDICT r3 weak #8; capacity guarantees each (expert, slot) gets
        at most one token, so the scatter is collision-free).
      - "ragged": sort tokens by expert and run the expert FFN as grouped
        matmuls (megablox gmm kernel on TPU, ``jax.lax.ragged_dot``
        elsewhere) — NO capacity padding and no
        [E, C, d] staging buffers in HBM (megablocks-class dropless
        semantics: every token reaches its top-k experts; ``capacity`` is
        ignored). Single-device / non-ep-sharded regime: under an ep mesh
        axis use einsum/scatter, whose dispatch GSPMD turns into the
        all_to_all. Requires ``expert_fn.forward_ragged``; falls back to
        scatter otherwise.
      - "auto": scatter when the dense one-hot buffers [n, E, C] would be
        large (> 16M elements — note C grows with n, so the einsum blows up
        quadratically in TOKEN count, independent of E) or when E >= 16.
    """
    from .gate import _load_balance_loss, topk_dispatch, topk_routing

    n, d = tokens.shape
    e = probs.shape[-1]
    if dispatch_mode == "auto":
        dispatch_mode = ("scatter" if e >= 16 or n * e * capacity > (1 << 24)
                         else "einsum")
    if (dispatch_mode in ("ragged", "pgmm")
            and getattr(expert_fn, "forward_" + dispatch_mode, None) is None):
        dispatch_mode = "scatter"
    if dispatch_mode in ("ragged", "pgmm"):
        # dropless top-k (no capacity): shared routing for both grouped paths
        w, eidx = jax.lax.top_k(probs, k)                        # [n, k]
        if renormalize and k > 1:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        aux = _load_balance_loss(
            probs, jax.nn.one_hot(eidx[:, 0], e, dtype=probs.dtype))
        flat_e = eidx.reshape(-1)                                # [n*k]
        if dispatch_mode == "pgmm":
            # Pallas padded grouped matmul: tile-aligned sorted layout
            from .....ops.grouped_matmul import padded_group_layout

            order, pos_sorted, tile_gids, p_total = padded_group_layout(
                flat_e, e, n * k)
            sorted_tokens = jnp.take(tokens, order // k, axis=0)
            x_pad = jnp.zeros((p_total, d), tokens.dtype).at[pos_sorted].set(
                sorted_tokens)
            out_pad = _raw(expert_fn.forward_pgmm(x_pad, tile_gids))
            out_sorted = jnp.take(out_pad, pos_sorted, axis=0)   # [n*k, d2]
        else:
            order = jnp.argsort(flat_e, stable=True)
            sorted_tokens = jnp.take(tokens, order // k, axis=0)  # [n*k, d]
            group_sizes = jax.ops.segment_sum(
                jnp.ones_like(flat_e), flat_e,
                num_segments=e).astype(jnp.int32)
            out_sorted = _raw(expert_fn.forward_ragged(
                sorted_tokens, group_sizes, jnp.take(flat_e, order)))
        inv = jnp.argsort(order, stable=True)
        out_flat = jnp.take(out_sorted, inv, axis=0).reshape(n, k, -1)
        out = jnp.einsum("nk,nkd->nd", w.astype(tokens.dtype), out_flat)
        return out, aux
    if dispatch_mode == "einsum":
        combine, dispatch, aux = topk_dispatch(probs, k, capacity, renormalize)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(tokens.dtype),
                               tokens)
        expert_in = constrain(expert_in, "expert", None, "embed")
        expert_out = _raw(expert_fn(expert_in))
        out = jnp.einsum("nec,ecd->nd", combine.astype(tokens.dtype),
                         expert_out)
        return out, aux
    if dispatch_mode != "scatter":
        raise ValueError(f"dispatch_mode must be auto/einsum/scatter/ragged/"
                         f"pgmm, got {dispatch_mode!r}")
    eidx, cpos, w, keep, aux = topk_routing(probs, k, capacity, renormalize)
    slot = (eidx * capacity + cpos).reshape(-1)                  # [n*k]
    kf = keep.astype(tokens.dtype).reshape(n * k, 1)
    # dropped choices carry kf=0 (no contribution) and w=0 (no combine);
    # their clamped slot ids are harmless
    contrib = jnp.broadcast_to(tokens[:, None, :], (n, k, d)).reshape(n * k, d)
    expert_in = jax.ops.segment_sum(contrib * kf, slot,
                                    num_segments=e * capacity)
    expert_in = constrain(expert_in.reshape(e, capacity, d),
                          "expert", None, "embed")
    expert_out = _raw(expert_fn(expert_in))
    d2 = expert_out.shape[-1]
    gathered = jnp.take(expert_out.reshape(e * capacity, d2), slot,
                        axis=0).reshape(n, k, d2)
    wk = (w * keep.astype(w.dtype)).astype(tokens.dtype)
    out = jnp.einsum("nk,nkd->nd", wk, gathered)
    return out, aux


class ExpertFFN(Layer):
    """Stacked per-expert FFN: weights carry a leading "expert" logical axis."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 activation: str = "gelu", dtype: str = "float32",
                 initializer_range: float = 0.02):
        super().__init__()
        self.num_experts = num_experts
        self.activation = activation
        init = I.Normal(std=initializer_range)
        self.w1 = annotate(
            self.create_parameter([num_experts, d_model, d_hidden], dtype=dtype,
                                  default_initializer=init),
            "expert", "embed", "expert_mlp")
        self.b1 = annotate(
            self.create_parameter([num_experts, d_hidden], dtype=dtype, is_bias=True),
            "expert", "expert_mlp")
        self.w2 = annotate(
            self.create_parameter([num_experts, d_hidden, d_model], dtype=dtype,
                                  default_initializer=init),
            "expert", "expert_mlp", "embed")
        self.b2 = annotate(
            self.create_parameter([num_experts, d_model], dtype=dtype, is_bias=True),
            "expert", "embed")

    def forward(self, x):
        """x: [E, C, d_model] — batched over the (ep-sharded) expert dim."""
        x = _raw(x)
        h = jnp.einsum("ecd,edm->ecm", x, self.w1._data) + self.b1._data[:, None, :]
        h = constrain(h, "expert", None, "expert_mlp")
        h = self._act(h)
        out = jnp.einsum("ecm,emd->ecd", h, self.w2._data) + self.b2._data[:, None, :]
        return constrain(out, "expert", None, "embed")

    def _act(self, h):
        if self.activation == "gelu":
            return jax.nn.gelu(h)
        if self.activation == "relu":
            return jax.nn.relu(h)
        if self.activation == "silu":
            return jax.nn.silu(h)
        raise ValueError(f"unknown activation {self.activation}")

    def forward_ragged(self, x, group_sizes, expert_ids):
        """Dropless grouped-matmul path (routed_ffn dispatch_mode="ragged"):
        x [m, d] sorted by expert, group_sizes [E] int32 row counts,
        expert_ids [m] the per-row expert (for the biases)."""
        from .....ops.grouped_matmul import grouped_dot

        x = _raw(x)
        h = grouped_dot(x, self.w1._data, group_sizes)
        h = self._act(h + jnp.take(self.b1._data, expert_ids, axis=0))
        out = grouped_dot(h, self.w2._data, group_sizes)
        return out + jnp.take(self.b2._data, expert_ids, axis=0)

    def forward_pgmm(self, x_pad, tile_gids, tile_m=None, interpret=False):
        """Pallas padded-grouped-matmul path (dispatch_mode="pgmm"); per-row
        biases follow the tile's expert id (pad rows get a bias too, but
        their outputs are never gathered back)."""
        from .....ops.grouped_matmul import TILE_M, pgmm

        tile_m = tile_m or TILE_M
        x_pad = _raw(x_pad)
        row_e = jnp.repeat(tile_gids, tile_m)
        h = pgmm(x_pad, self.w1._data, tile_gids, tile_m, interpret)
        h = self._act(h + jnp.take(self.b1._data, row_e, axis=0))
        out = pgmm(h, self.w2._data, tile_gids, tile_m, interpret)
        return out + jnp.take(self.b2._data, row_e, axis=0)


class SwiGLUExpertFFN(Layer):
    """Llama/Mixtral-style gated experts (swiglu), stacked over the expert axis."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 dtype: str = "float32", initializer_range: float = 0.02):
        super().__init__()
        self.num_experts = num_experts
        init = I.Normal(std=initializer_range)
        mk = lambda shape: self.create_parameter(shape, dtype=dtype,
                                                 default_initializer=init)
        self.w_gate = annotate(mk([num_experts, d_model, d_hidden]),
                               "expert", "embed", "expert_mlp")
        self.w_up = annotate(mk([num_experts, d_model, d_hidden]),
                             "expert", "embed", "expert_mlp")
        self.w_down = annotate(mk([num_experts, d_hidden, d_model]),
                               "expert", "expert_mlp", "embed")

    def forward(self, x):
        x = _raw(x)
        g = jnp.einsum("ecd,edm->ecm", x, self.w_gate._data)
        u = jnp.einsum("ecd,edm->ecm", x, self.w_up._data)
        h = constrain(jax.nn.silu(g) * u, "expert", None, "expert_mlp")
        out = jnp.einsum("ecm,emd->ecd", h, self.w_down._data)
        return constrain(out, "expert", None, "embed")

    def forward_ragged(self, x, group_sizes, expert_ids):
        """Dropless grouped swiglu (dispatch_mode="ragged"): megablox gmm
        kernel on TPU / lax.ragged_dot elsewhere — no capacity padding, no
        [E, C, d] staging in HBM."""
        from .....ops.grouped_matmul import grouped_dot

        x = _raw(x)
        g = grouped_dot(x, self.w_gate._data, group_sizes)
        u = grouped_dot(x, self.w_up._data, group_sizes)
        return grouped_dot(jax.nn.silu(g) * u, self.w_down._data,
                           group_sizes)

    def forward_dense(self, tokens, gate_of):
        """Dropless form for a few rows: every expert multiplies every
        token and reads its weights once. tokens [n, d]; ``gate_of`` [n, E]
        float32, a token's gate for each expert, 0 where it was not chosen.
        Returns the gated sum [n, d]."""
        g = jnp.einsum("nd,edf->nef", tokens, self.w_gate._data)
        u = jnp.einsum("nd,edf->nef", tokens, self.w_up._data)
        act = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
               * gate_of[:, :, None]).astype(tokens.dtype)
        return jnp.einsum("nef,efd->nd", act, self.w_down._data)

    def forward_pgmm(self, x_pad, tile_gids, tile_m=None, interpret=False):
        """Dropless grouped swiglu via the Pallas padded grouped matmul
        (dispatch_mode="pgmm", ops/grouped_matmul.py)."""
        from .....ops.grouped_matmul import TILE_M, pgmm

        tile_m = tile_m or TILE_M
        x_pad = _raw(x_pad)
        g = pgmm(x_pad, self.w_gate._data, tile_gids, tile_m, interpret)
        u = pgmm(x_pad, self.w_up._data, tile_gids, tile_m, interpret)
        return pgmm(jax.nn.silu(g) * u, self.w_down._data, tile_gids,
                    tile_m, interpret)


class Relu2ExpertFFN(Layer):
    """Experts of two matrices, not gated: ``relu(x W_up)^2 W_down``
    (Nemotron-H's ``relu2``), stacked over the expert axis, no bias."""

    def __init__(self, num_experts: int, d_model: int, d_hidden: int,
                 dtype: str = "float32", initializer_range: float = 0.02):
        super().__init__()
        self.num_experts = num_experts
        init = I.Normal(std=initializer_range)
        mk = lambda shape: self.create_parameter(shape, dtype=dtype,
                                                 default_initializer=init)
        self.w_up = annotate(mk([num_experts, d_model, d_hidden]),
                             "expert", "embed", "expert_mlp")
        self.w_down = annotate(mk([num_experts, d_hidden, d_model]),
                               "expert", "expert_mlp", "embed")

    def forward(self, x):
        """x [E, C, d_model], batched over the expert axis."""
        x = _raw(x)
        u = jnp.einsum("ecd,edm->ecm", x, self.w_up._data)
        h = constrain(jnp.square(jax.nn.relu(u)), "expert", None,
                      "expert_mlp")
        out = jnp.einsum("ecm,emd->ecd", h, self.w_down._data)
        return constrain(out, "expert", None, "embed")

    def forward_ragged(self, x, group_sizes, expert_ids):
        """Dropless grouped form: x [m, d] sorted by expert."""
        from .....ops.grouped_matmul import grouped_dot

        x = _raw(x)
        u = grouped_dot(x, self.w_up._data, group_sizes)
        return grouped_dot(jnp.square(jax.nn.relu(u)), self.w_down._data,
                           group_sizes)

    def forward_dense(self, tokens, gate_of):
        """Dropless form for a few rows (``SwiGLUExpertFFN.forward_dense``):
        tokens [n, d], ``gate_of`` [n, E] float32; returns [n, d]."""
        u = jnp.einsum("nd,edf->nef", tokens, self.w_up._data)
        act = (jnp.square(jax.nn.relu(u.astype(jnp.float32)))
               * gate_of[:, :, None]).astype(tokens.dtype)
        return jnp.einsum("nef,efd->nd", act, self.w_down._data)


class MoELayer(Layer):
    """Mixture of Experts (reference moe_layer.py:263).

    Args:
        d_model: hidden size.
        num_experts: total number of experts (the reference's
            ``num_expert * world_size`` — one global count here; the ep mesh
            axis shards them).
        experts: optional stacked expert Layer (``[E, C, d] -> [E, C, d]``);
            default builds :class:`ExpertFFN` with ``d_hidden``.
        gate: "gshard" | "switch" | "naive" or a BaseGate instance.
        top_k: experts per token (gshard=2, switch=1).
        capacity_factor: per-expert capacity = ceil(tokens * k * cf / E).
    """

    def __init__(self, d_model: int, num_experts: int, d_hidden: Optional[int] = None,
                 experts: Optional[Layer] = None, gate: str = "gshard",
                 top_k: Optional[int] = None, capacity_factor: Optional[float] = None,
                 activation: str = "gelu", dtype: str = "float32",
                 recompute_interval: int = 0, group=None,
                 dispatch_mode: str = "auto"):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        # "einsum" (GShard dense — GSPMD lowers it to alltoall under ep
        # sharding), "scatter" (sparse O(n*k*d) dispatch), or "auto"
        # (scatter when E >= 16 OR the dense one-hot buffers would exceed
        # 16M elements — they are O(n^2 k) in tokens and OOM first; ep-mesh
        # users preferring the alltoall lowering at large n can force
        # dispatch_mode="einsum")
        self.dispatch_mode = dispatch_mode
        # capacity precedence: explicit arg > the gate's capacity (reference
        # GShardGate(capacity=...) API) > 1.25 default
        if capacity_factor is None and isinstance(gate, BaseGate):
            capacity_factor = getattr(gate, "capacity_factor", None)
        self.capacity_factor = 1.25 if capacity_factor is None else capacity_factor
        self.experts = experts if experts is not None else ExpertFFN(
            num_experts, d_model, d_hidden or 4 * d_model, activation, dtype)
        if isinstance(gate, BaseGate):
            self.gate = gate
            self.top_k = getattr(gate, "top_k", top_k or 2)
        elif gate == "gshard":
            self.top_k = top_k or 2
            self.gate = GShardGate(d_model, num_experts, topk=self.top_k)
        elif gate == "switch":
            self.top_k = 1
            self.gate = SwitchGate(d_model, num_experts)
        elif gate in ("naive", "topk"):
            self.top_k = top_k or 2
            self.gate = NaiveGate(d_model, num_experts, topk=self.top_k)
        else:
            raise ValueError(f"unknown gate {gate!r}")

    def capacity(self, num_tokens: int) -> int:
        cap = int(math.ceil(num_tokens * self.top_k * self.capacity_factor
                            / self.num_experts))
        return max(cap, self.top_k)

    def _routed_forward(self, x, *param_arrays):
        """The whole MoE computation as one pure fn (one taped op in eager)."""
        from .....jit.api import _Swap

        tensors = [t for _, t in self.named_parameters()]
        with _Swap(tensors, param_arrays):
            x = jnp.asarray(x)
            orig_shape = x.shape
            tokens = x.reshape(-1, orig_shape[-1])
            cap = self.capacity(tokens.shape[0])
            p = self.gate.probs(tokens)
            out, aux = routed_ffn(tokens, p, self.experts, self.top_k, cap,
                                  getattr(self.gate, "renormalize", True),
                                  dispatch_mode=self.dispatch_mode)
            if not getattr(self.gate, "use_aux", True):
                aux = jnp.zeros((), jnp.float32)
            out = out.reshape(orig_shape)
            if out.ndim == 3:
                out = constrain(out, "batch", "seq", "embed")
        return out, aux

    def forward(self, x):
        """x: [batch, seq, d_model] (or [tokens, d_model]). Returns the same
        kind as the input (Tensor in -> Tensor out, raw array in -> raw out)."""
        from .....core.op_registry import apply_fn

        was_tensor = isinstance(x, Tensor)
        tensors = [t for _, t in self.named_parameters()]
        out, aux = apply_fn("moe", self._routed_forward, x, *tensors)
        self.gate.set_loss(aux if was_tensor else _raw(aux))
        return out if was_tensor else _raw(out)

    def get_loss(self, clear=True):
        """The gate's aux (load-balance) loss for this forward."""
        return self.gate.get_loss(clear=clear)


# ---- dropless routed FFN: the choice and the gates come from the gate ------

#: rows up to which the dense arm serves. Measured on the v5e at 64 rows
#: only (64 experts of 2048 x 1536, a layer): dense 1.67 ms, 88% of the time
#: the experts' bytes take, sorted 3.76 ms (PERF.md section 6, PR 28). Above
#: that, reckoned and not measured: the dense arm multiplies every row by
#: every expert (1.2 GFLOP a row), so it leaves the weights' read time at
#: about 150 rows and meets the sorted arm's 3.8 ms between 370 and 610
#: rows; 256 stays under that. `chipbench/scratch/moe_arm_sweep.py` reads
#: the crossover when a chip run of it is made.
_DENSE_ROWS = 256


def dropless_arm(rows: int) -> str:
    """Which dispatch serves ``rows`` tokens, from the count alone: "dense"
    (every local expert for every row, the unchosen weighted 0: one read of
    the weights, no sort, no gather; the decode regime) or "sorted" (pairs
    sorted by expert into a grouped matmul: FLOPs for the chosen pairs
    only; the prefill pack)."""
    return "dense" if rows <= _DENSE_ROWS else "sorted"


def dropless_ffn(tokens, expert_idx, gates, experts, first: int = 0):
    """Routed FFN with no capacity: every chosen (token, expert) pair whose
    expert this layer holds is computed, none is dropped.

    tokens [n, d]; expert_idx [n, k] int32 over ALL experts; gates [n, k]
    float32; ``experts`` a :class:`SwiGLUExpertFFN` (three matrices, gated)
    or a :class:`Relu2ExpertFFN` (two, ``relu^2``) holding experts
    ``[first, first + experts.num_experts)``: the stack does the arithmetic
    (``forward_dense`` or ``forward_ragged``). Returns this share's part of
    the layer's output [n, d] (the shares of a partition of the experts sum
    to the whole layer: expert parallelism's form) and ``rows`` [local
    experts] int32, the rows each local expert got."""
    n, d = tokens.shape
    k = expert_idx.shape[1]
    e = experts.num_experts
    local = expert_idx - first
    mine = (local >= 0) & (local < e)
    gates = jnp.where(mine, gates, 0.0)
    local = jnp.where(mine, local, e)              # e: nobody's
    rows = jnp.zeros((e + 1,), jnp.int32).at[local.reshape(-1)].add(1)[:e]
    with jax.named_scope("pt.moe.experts"):
        if dropless_arm(n) == "dense":
            # [n, e]: a token's gate for each local expert, 0 if unchosen
            gate_of = jnp.einsum(
                "nk,nke->ne", gates,
                jax.nn.one_hot(local, e, dtype=jnp.float32))
            out = experts.forward_dense(tokens, gate_of)
        else:
            flat = local.reshape(-1)                              # [n*k]
            order = jnp.argsort(flat, stable=True)   # nobody's rows last
            x = jnp.take(tokens, order // k, axis=0)
            y = experts.forward_ragged(x, rows, None)
            w = jnp.take(gates.reshape(-1), order)
            y = jnp.where((w > 0)[:, None], y.astype(jnp.float32)
                          * w[:, None], 0.0)
            out = jnp.zeros((n, d), jnp.float32).at[order // k].add(y)
            out = out.astype(tokens.dtype)
    return out, rows


class DroplessMoE(Layer):
    """Expert layer whose gate hands over the choice and the gates
    (``gate.route``): sigmoid or softmax scores, bias-corrected or not, are
    the gate's business; here every chosen pair is computed. The layer
    routes over all ``num_experts`` and holds (and computes) the experts
    ``[first, first + count)``, by default all of them. One dispatch is
    picked from the row count (``dropless_arm``).

    ``forward(x)`` returns the layer's (share of the) output with x's
    shape; ``forward(x, with_rows=True)`` also returns the rows each local
    expert got ([count] int32), for the serving engine's counters.

    ``experts`` is the stack of the ``count`` local experts where they are
    not SwiGLU ones (a :class:`Relu2ExpertFFN`); ``shared`` a layer every
    token goes through beside the routed ones (``x -> [.., d_model]``),
    added whole to this share's output: what every chip of an
    expert-parallel layer computes alike."""

    def __init__(self, d_model: int, num_experts: int, d_hidden: int,
                 gate: BaseGate, first: int = 0, count: Optional[int] = None,
                 dtype: str = "float32", initializer_range: float = 0.02,
                 experts: Optional[Layer] = None,
                 shared: Optional[Layer] = None):
        super().__init__()
        count = num_experts - first if count is None else int(count)
        if not 0 <= first <= first + count <= num_experts:
            raise ValueError(f"experts [{first}, {first + count}) are not "
                             f"among {num_experts}")
        self.num_experts, self.first = int(num_experts), int(first)
        self.gate = gate
        self.top_k = gate.top_k
        if experts is None:
            experts = SwiGLUExpertFFN(count, d_model, d_hidden, dtype=dtype,
                                      initializer_range=initializer_range)
        elif experts.num_experts != count:
            raise ValueError(f"the stack holds {experts.num_experts} "
                             f"experts, the layer {count}")
        self.experts = experts
        self.shared = shared

    @functools.partial(jax.named_call, name="pt.moe")
    def forward(self, x, with_rows: bool = False):
        x = _raw(x)
        tokens = x.reshape(-1, x.shape[-1])
        with jax.named_scope("pt.moe.router"):
            idx, gates = self.gate.route(tokens)
        out, rows = dropless_ffn(tokens, idx, gates, self.experts,
                                 self.first)
        if self.shared is not None:
            with jax.named_scope("pt.moe.shared"):
                out = out + _raw(self.shared(tokens))
        out = out.reshape(x.shape)
        return (out, rows) if with_rows else out
