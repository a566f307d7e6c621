"""Mamba-2's selective state space (SSD): the chunked scan and its one-token
step, in ``jax.numpy`` under ``jax.named_scope`` (no Pallas kernel yet:
PERF.md section 5 says what the XLA form reads against its roofline).

The recurrence, a head ``h`` of ``P`` channels over a state of ``N``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S [H, P, N] float32
    y_t = S_t C_t + D x_t

``x`` [.., H, P]; ``dt`` [.., H] (after softplus, >= 0); ``A`` [H] (< 0);
``B``, ``C`` [.., G, N], head ``h`` reading group ``h // (H // G)``; ``D``
[H]. A position with ``dt = 0`` leaves the state as it was and adds nothing:
that is how a padded tail leaves no trace (the caller zeroes its ``dt``).

Chunked (the duality): with ``cum_t`` the running sum of ``dt A`` inside a
chunk of ``L`` positions,

    y_t   = exp(cum_t) S_in C_t
            + sum_{s<=t} exp(cum_t - cum_s) dt_s (C_t . B_s) x_s + D x_t
    S_out = exp(cum_L) S_in + sum_s exp(cum_L - cum_s) dt_s x_s (x) B_s

so inside a chunk the work is matmuls over ``[L, L]`` (``chunk_terms``),
and between chunks only the state passes. ``ssd_scan`` passes it along each
row of independent sequences; ``ssd_scan_pooled`` serves a pool ``[slots, H,
P, N]`` kept a SEQUENCE (``ops.paged_attention.SeqState``): the chunks of
the serving engine's packed rows, the rows of one sequence adjacent and
rising, so that several chunks of one prompt in one call chain through a
carry of one sequence's state; a slot's state is gathered before the scan
and the last of its run scattered back after it. ``ssd_step`` is the
one-token form over the same pool. The cumulative sums, the decays and the
state are float32 whatever the model's type; the matmuls take ``x``'s type
and accumulate in float32 (the state is rounded to it where it is a
matmul's operand, as the published kernels do).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk (the published ``chunk_size``)
CHUNK = 128


def _grouped(a, groups: int):
    """[.., H, ...] -> [.., G, H // G, ...] on the axis after the chunk's
    two leading ones ([n, L, H, ..])."""
    n, l, h = a.shape[:3]
    return a.reshape(n, l, groups, h // groups, *a.shape[3:])


def chunk_terms(x, dt, A, B, C):
    """What a chunk gives without the state it starts from. ``x`` [n, L, H,
    P], ``dt`` [n, L, H] float32, ``B``/``C`` [n, L, G, N]. Returns
    ``y_diag`` [n, L, H, P] float32 (the quadratic form), ``local`` [n, H,
    P, N] float32 (the chunk's own contribution to ``S_out``) and ``cum``
    [n, L, H] float32."""
    n, l, h, p = x.shape
    g = B.shape[2]
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum(dt * A.astype(jnp.float32), axis=1)        # [n, L, H]
    cb = jnp.einsum("nlgk,nsgk->ngls", C, B,
                    preferred_element_type=jnp.float32)         # [n, G, L, L]
    cum_g = _grouped(cum, g)                                    # [n, L, G, R]
    diff = (cum_g.transpose(0, 2, 3, 1)[..., :, None]
            - cum_g.transpose(0, 2, 3, 1)[..., None, :])        # [n,G,R,L,L]
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    dt_g = _grouped(dt, g).transpose(0, 2, 3, 1)                # [n, G, R, L]
    m = cb[:, :, None] * decay * dt_g[..., None, :]             # [n,G,R,L,L]
    x_g = _grouped(x, g)                                        # [n,L,G,R,P]
    y_diag = jnp.einsum("ngrls,nsgrp->nlgrp", m.astype(x.dtype), x_g,
                        preferred_element_type=jnp.float32)
    w = jnp.exp(cum_g[:, -1:] - cum_g) * _grouped(dt, g)        # [n, L, G, R]
    local = jnp.einsum("nsgrp,nsgk->ngrpk",
                       (x_g * w[..., None].astype(x.dtype)), B,
                       preferred_element_type=jnp.float32)
    return (y_diag.reshape(n, l, h, p), local.reshape(n, h, p, -1), cum)


def chunk_out(y_diag, cum, x, C, D, s_in):
    """``y`` [n, L, H, P] (x's type) of chunks that start from ``s_in`` [n,
    H, P, N] float32."""
    n, l, h, p = x.shape
    g = C.shape[2]
    s_g = s_in.reshape(n, g, h // g, p, -1).astype(x.dtype)
    y_off = jnp.einsum("ngrpk,nlgk->nlgrp", s_g, C,
                       preferred_element_type=jnp.float32)
    y = (y_diag + y_off.reshape(n, l, h, p) * jnp.exp(cum)[..., None]
         + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32))
    return y.astype(x.dtype)


def _chunked(a, chunk):
    """[b, s, ..] -> [b * s // chunk, chunk, ..]."""
    return a.reshape(-1, chunk, *a.shape[2:])


def _pad_to(a, s):
    return jnp.pad(a, ((0, 0), (0, s - a.shape[1])) + ((0, 0),) * (a.ndim - 2))


def ssd_scan(x, dt, A, B, C, D, init=None, chunk: int = CHUNK):
    """Independent sequences a row: ``x`` [b, s, H, P] from ``init`` [b, H,
    P, N] (zeros when None). Returns ``y`` [b, s, H, P] and the state after
    position ``s - 1`` [b, H, P, N] float32. ``s`` need be no multiple of
    the chunk (the tail is padded with ``dt = 0``)."""
    b, s, h, p = x.shape
    l = min(chunk, s)
    full = -(-s // l) * l
    x_, dt_, B_, C_ = (_pad_to(a, full) for a in (x, dt, B, C))
    nc = full // l
    with jax.named_scope("pt.ssm.scan"):
        y_diag, local, cum = chunk_terms(*(_chunked(a, l) for a in (x_, dt_)),
                                         A, *(_chunked(a, l) for a in (B_, C_)))
        total = jnp.exp(cum[:, -1]).reshape(b, nc, h)
        local = local.reshape(b, nc, *local.shape[1:])
        s0 = (jnp.zeros(local.shape[:1] + local.shape[2:], jnp.float32)
              if init is None else init.astype(jnp.float32))

        def pass_on(state, inp):
            keep, add = inp
            return keep[..., None, None] * state + add, state

        final, s_in = jax.lax.scan(
            pass_on, s0, (total.swapaxes(0, 1), local.swapaxes(0, 1)))
        s_in = s_in.swapaxes(0, 1).reshape(b * nc, *s_in.shape[2:])
        y = chunk_out(y_diag, cum, _chunked(x_, l), _chunked(C_, l), D, s_in)
    return y.reshape(b, full, h, p)[:, :s], final


def ssd_scan_pooled(pool, x, dt, A, B, C, D, slots, fresh, count,
                    chunk: int = CHUNK):
    """The packed chunk: row ``r`` of ``x`` [b, s, H, P] continues the
    sequence whose state is ``pool[slots[r]]`` ([slots, H, P, N] float32),
    from zero where ``fresh[r]`` (the row starts its sequence), over its
    first ``count[r]`` positions (the caller has zeroed ``dt`` past them).
    The rows of one sequence are ADJACENT and rising (the engine orders a
    pack so and checks it, PT-SRV-011): a run of rows with one slot reads
    the slot's state once, before the scan, passes it on from row to row
    through a carry of ONE state, and the run's last row writes it back,
    in one scatter after the scan. Two runs with one slot would both start
    from what the pool held, and which of them is kept is undefined. A row
    with ``count == 0`` passes on what it got, and a run of such rows only
    (the pack's dummy rows, whatever their slot) writes nothing. Returns
    ``y`` [b, s, H, P] and the pool."""
    b, s, h, p = x.shape
    l = min(chunk, s)
    if s % l:
        raise ValueError(f"a packed row of {s} positions is no whole number "
                         f"of chunks of {l}")
    nc = s // l
    n_slots = pool.shape[0]
    with jax.named_scope("pt.ssm.scan"):
        y_diag, local, cum = chunk_terms(*(_chunked(a, l) for a in (x, dt)),
                                         A, *(_chunked(a, l) for a in (B, C)))
        total = jnp.exp(cum[:, -1])                               # [b*nc, H]
        first = jnp.arange(nc, dtype=jnp.int32)[None, :] * l     # [1, nc]
        slot_c = jnp.repeat(jnp.clip(slots, 0, n_slots - 1), nc)
        fresh_c = (fresh[:, None] & (first == 0)).reshape(-1)
        live_c = (count[:, None] > first).reshape(-1)
        edge = slot_c[1:] != slot_c[:-1]
        head = jnp.concatenate([jnp.ones((1,), bool), edge])
        last = jnp.concatenate([edge, jnp.ones((1,), bool)])
        init = pool[slot_c]                                 # [b*nc, H, P, N]

        def pass_on(carry, inp):
            state, wrote = carry
            is_head, is_fresh, live, keep, add, own = inp
            old = jnp.where(is_head, own, state)
            start = jnp.where(is_fresh, 0.0, old)
            new = jnp.where(live, keep[:, None, None] * start + add, old)
            wrote = live | (wrote & ~is_head)
            return (new, wrote), (start, new, wrote)

        _, (s_in, new, wrote) = jax.lax.scan(
            pass_on, (jnp.zeros(pool.shape[1:], pool.dtype), False),
            (head, fresh_c, live_c, total, local, init))
        pool = pool.at[jnp.where(last & wrote, slot_c, n_slots)].set(
            new, mode="drop")
        y = chunk_out(y_diag, cum, _chunked(x, l), _chunked(C, l), D, s_in)
        # the next layer waits for the scatter: left free, the v5e
        # compiler puts every layer's at the program's end and keeps their
        # stacked states till then (3.6 GB of temporaries for 1.0, 23 layers)
        y, pool = jax.lax.optimization_barrier((y, pool))
    return y.reshape(b, s, h, p), pool


def ssd_step(pool, x, dt, A, B, C, D, fresh, slots=None, live=None):
    """One token a row: ``x`` [b, H, P], ``dt`` [b, H], ``B``/``C`` [b, G,
    N]; ``fresh`` [b] bool starts a row from zero (its position is 0).
    Either row ``i`` is slot ``i`` and ``live`` [b] bool says which rows
    decode (the decode block: the others' states stay as they are, and the
    pool is rewritten in one pass, in place when donated), or ``slots`` [b]
    names each row's slot, one out of range changing nothing (the
    first-token program's rows). Returns ``y`` [b, H, P] and the pool."""
    b, h, p = x.shape
    g = B.shape[1]
    with jax.named_scope("pt.ssm.step"):
        if slots is None:
            old = pool
        else:
            old = pool[jnp.clip(slots, 0, pool.shape[0] - 1)]
        dt = dt.astype(jnp.float32)
        keep = jnp.exp(dt * A.astype(jnp.float32))                # [b, H]
        xf = (x.astype(jnp.float32) * dt[..., None])              # [b, H, P]
        b_h = jnp.repeat(B.astype(jnp.float32), h // g, axis=1)   # [b, H, N]
        c_h = jnp.repeat(C.astype(jnp.float32), h // g, axis=1)
        start = jnp.where(fresh[:, None, None, None], 0.0, old)
        new = (keep[..., None, None] * start
               + xf[..., None] * b_h[:, :, None, :])
        y = (jnp.sum(new * c_h[:, :, None, :], axis=-1)
             + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32))
        if slots is None:
            pool = jnp.where(live[:, None, None, None], new, old)
        else:
            pool = pool.at[slots].set(new, mode="drop")
    return y.astype(x.dtype), pool
