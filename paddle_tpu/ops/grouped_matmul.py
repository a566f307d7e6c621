"""Grouped (per-expert) matmul — Pallas TPU kernels for MoE expert FFNs.

The megablocks-class dropless regime (reference MoE dispatches with NCCL
alltoall + per-expert GEMMs, incubate/distributed/models/moe/moe_layer.py:263;
``jax.lax.ragged_dot`` measured SLOWER than the capacity-scatter dispatch on
v5e — benchmarks/moe_ab.py): tokens are sorted by expert and PADDED so each
expert's rows start at a tile boundary, then

  - ``pgmm(x, w, tile_gids)``: out[r] = x[r] @ w[g(r)] as one Pallas kernel —
    grid (m_tiles, n_tiles, k_tiles), each m-tile belongs to exactly ONE
    expert (the padding guarantee), whose weight block the index_map selects
    via the scalar-prefetched per-tile group id. fp32 VMEM accumulator across
    the k steps.
  - ``pgmm_dw(x, dout, tile_gids)``: dw[e] = x_e^T @ dout_e — grid
    (k_tiles, n_tiles, m_tiles) with m innermost; tiles of one expert are
    CONTIGUOUS (sorted rows), so the output block for expert e stays resident
    while its m-tiles accumulate and flushes exactly once.

Both are wired into a custom_vjp (``pgmm`` differentiates w.r.t. x and w), so
``routed_ffn(dispatch_mode="pgmm")`` trains. Padding cost is bounded by
E * (tile_m - 1) rows — static shapes throughout (XLA requirement), vs the
capacity formulation's multiplicative 1.25x on EVERY row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_M = 512
TILE_N = 512
TILE_K = 512


def _pgmm_kernel(gids_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fit_tile(pref, dim):
    """Largest of pref/512/256/128 dividing dim, else the whole dim."""
    for c in (pref, 512, 256, 128):
        if c <= dim and dim % c == 0:
            return c
    return dim


def _pgmm_raw(x, w, tile_gids, tile_m, interpret=False):
    """x [P, k] (P % tile_m == 0), w [E, k, n], tile_gids [P // tile_m] int32
    -> [P, n] with out rows of tile t multiplied by w[tile_gids[t]]."""
    from jax.experimental.pallas import tpu as pltpu

    p, kdim = x.shape
    e, _, n = w.shape
    tm = tile_m
    tn = _fit_tile(TILE_N, n)
    tk = _fit_tile(TILE_K, kdim)
    assert p % tm == 0 and n % tn == 0 and kdim % tk == 0
    nk = kdim // tk
    grid = (p // tm, n // tn, nk)
    kernel = functools.partial(_pgmm_kernel, nk=nk)
    with jax.named_scope("pt_grouped_matmul"):
        return pl.pallas_call(
            kernel,
            name="pt_grouped_matmul",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((tm, tk), lambda i, j, kk, g: (i, kk)),
                    pl.BlockSpec((1, tk, tn),
                                 lambda i, j, kk, g: (g[i], kk, j)),
                ],
                out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, g: (i, j)),
                scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((p, n), x.dtype),
            interpret=interpret,
        )(tile_gids, x, w)


def _pgmm_dw_kernel(gids_ref, x_ref, g_ref, dw_ref, *, nm):
    mi = pl.program_id(2)
    contrib = jax.lax.dot_general(
        x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # first m-tile of this expert initializes its (resident) output block;
    # subsequent contiguous tiles accumulate in place
    prev = gids_ref[jnp.maximum(mi - 1, 0)]
    first = (mi == 0) | (gids_ref[mi] != prev)

    @pl.when(first)
    def _():
        dw_ref[0] = contrib.astype(dw_ref.dtype)

    @pl.when(~first)
    def _():
        dw_ref[0] = (dw_ref[0].astype(jnp.float32) + contrib).astype(
            dw_ref.dtype)


def _pgmm_dw_raw(x, dout, tile_gids, e, tile_m, interpret=False):
    """dw[e] = sum over rows r with g(r)==e of x[r]^T dout[r].
    x [P, k], dout [P, n] -> [E, k, n] fp32.

    Experts owning NO m-tile (zero tokens this step under
    ``padded_group_layout``, which gives an empty expert zero padded rows)
    never run the kernel's init branch, so on real hardware their output
    blocks would be whatever was in the buffer — uninitialized memory
    flowing into dw (ADVICE round-5 high). ``_mask_unvisited_experts``
    zeroes exactly those blocks; interpret mode happens to zero-fill
    outputs, which is why the bug only bites in non-interpret mode."""
    dw = _pgmm_dw_call(x, dout, tile_gids, e, tile_m, interpret)
    return _mask_unvisited_experts(dw, tile_gids, e)


def _mask_unvisited_experts(dw, tile_gids, e):
    """Zero dw blocks of experts that own no m-tile (their correct gradient:
    no rows routed to them contributes nothing). Tile counts come straight
    from ``tile_gids`` — an expert absent from it was never visited by the
    grid, so its block was never written."""
    counts = jnp.zeros((e,), jnp.int32).at[tile_gids].add(1)
    return jnp.where((counts > 0)[:, None, None], dw,
                     jnp.zeros((), dw.dtype))


def _pgmm_dw_call(x, dout, tile_gids, e, tile_m, interpret=False):
    from jax.experimental.pallas import tpu as pltpu

    p, kdim = x.shape
    _, n = dout.shape
    tm = tile_m
    tn = _fit_tile(TILE_N, n)
    tk = _fit_tile(TILE_K, kdim)
    assert p % tm == 0 and n % tn == 0 and kdim % tk == 0
    nm = p // tm
    grid = (kdim // tk, n // tn, nm)   # m innermost: same-expert tiles are
    kernel = functools.partial(_pgmm_dw_kernel, nm=nm)  # consecutive
    with jax.named_scope("pt_grouped_matmul_bwd"):
        return pl.pallas_call(
            kernel,
            name="pt_grouped_matmul_bwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((tm, tk), lambda i, j, mi, g: (mi, i)),
                    pl.BlockSpec((tm, tn), lambda i, j, mi, g: (mi, j)),
                ],
                out_specs=pl.BlockSpec((1, tk, tn),
                                       lambda i, j, mi, g: (g[mi], i, j)),
            ),
            out_shape=jax.ShapeDtypeStruct((e, kdim, n), jnp.float32),
            interpret=interpret,
        )(tile_gids, x, dout)


def _gid_zero_cot(gids):
    import numpy as _np

    return _np.zeros(gids.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def pgmm(x, w, tile_gids, tile_m=TILE_M, interpret=False):
    """Padded grouped matmul: rows of m-tile t hit w[tile_gids[t]].

    x [P, k] sorted-by-group and tile-aligned (pad rows zero), w [E, k, n],
    tile_gids [P // tile_m] int32 (monotone non-decreasing). Differentiable
    w.r.t. x and w (pad rows are zero, so they contribute nothing to dw and
    receive garbage-free dx)."""
    return _pgmm_raw(x, w, tile_gids, tile_m, interpret)


def _pgmm_fwd(x, w, tile_gids, tile_m, interpret):
    return _pgmm_raw(x, w, tile_gids, tile_m, interpret), (x, w, tile_gids)


def _pgmm_bwd(tile_m, interpret, res, g):
    x, w, tile_gids = res
    g = g.astype(x.dtype)
    # dx[r] = g[r] @ w[g(r)]^T — the same pgmm over transposed weights
    dx = _pgmm_raw(g, jnp.swapaxes(w, 1, 2), tile_gids, tile_m, interpret)
    dw = _pgmm_dw_raw(x, g, tile_gids, w.shape[0], tile_m, interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype), _gid_zero_cot(tile_gids)


pgmm.defvjp(_pgmm_fwd, _pgmm_bwd)


def grouped_dot(x, w, group_sizes):
    """Grouped matmul over rows sorted by group (group_sizes [E] row
    counts): jax's megablox ``gmm`` Pallas kernel on TPU (the tuned
    megablocks-class kernel — weight-stationary tiling, no padding between
    groups), ``lax.ragged_dot`` elsewhere. Both differentiate w.r.t. x and
    w. The kernel takes whole row tiles only: a row count that is no
    multiple of the tile is filled up with rows that belong to no group
    (they come after every group's) and cut off the result."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        m, k, n = x.shape[0], w.shape[1], w.shape[2]
        tm = 512
        tiling = (tm, _fit_tile(512, k), _fit_tile(512, n))
        fill = -m % tm
        if fill:
            x = jnp.pad(x, ((0, fill), (0, 0)))
        # preferred_element_type and tiling are positional: they are the
        # custom_vjp's non-differentiable arguments
        return gmm(x, w, group_sizes, x.dtype, tiling)[:m]
    return jax.lax.ragged_dot(x, w, group_sizes)


def padded_group_layout(flat_e, e, n_rows, tile_m=None):
    """Static-shape padded layout for sorted-by-expert rows.

    flat_e [n_rows] int32 expert ids (NOT necessarily sorted). Returns
    (order, padded_pos [n_rows], tile_gids [P//tile_m], P) where P is the
    STATIC worst-case padded length n_rows_padded + e*tile_m; row
    ``order[r]`` of the original goes to padded row ``padded_pos[r]``; tiles
    are owned by exactly one expert each (pad tail tiles are assigned to the
    last expert over zero rows)."""
    tile_m = tile_m or TILE_M
    p_total = ((n_rows + tile_m - 1) // tile_m) * tile_m + e * tile_m
    order = jnp.argsort(flat_e, stable=True)                 # [n]
    se = jnp.take(flat_e, order)                             # sorted experts
    gs = jax.ops.segment_sum(jnp.ones_like(flat_e), flat_e,
                             num_segments=e)                 # [e]
    padded = ((gs + tile_m - 1) // tile_m) * tile_m
    pad_off = jnp.concatenate([jnp.zeros(1, padded.dtype),
                               jnp.cumsum(padded)[:-1]])     # [e]
    off = jnp.concatenate([jnp.zeros(1, gs.dtype),
                           jnp.cumsum(gs)[:-1]])             # [e]
    rank = jnp.arange(n_rows, dtype=jnp.int32) - jnp.take(off, se)
    pos_sorted = jnp.take(pad_off, se) + rank                # [n]
    # tile ownership: tile t belongs to expert e iff t*tile_m < pad_end[e]
    ends = jnp.cumsum(padded)                                # [e]
    tiles = jnp.arange(p_total // tile_m, dtype=jnp.int32) * tile_m
    tile_gids = jnp.searchsorted(ends, tiles, side="right").astype(jnp.int32)
    tile_gids = jnp.minimum(tile_gids, e - 1)
    return order, pos_sorted, tile_gids, p_total
