"""SPMD pipeline parallelism over a ``pp`` mesh axis.

Parity anchor: the reference's dygraph pipeline engine
(/root/reference/python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:231,
forward_backward_pipeline 1F1B at :547, interleaved VPP at :1143) and its P2P layer
(pp_utils/p2p_communication.py:51 SendRecvMeta shape negotiation).

TPU-native redesign: no per-rank Python schedule, no NCCL P2P, no shape
negotiation. The whole pipeline is ONE jitted SPMD program:

  - layer weights are STACKED along a leading axis sharded over the ``pp`` mesh
    axis — each device materialises only its stage's layers;
  - ``jax.shard_map`` with ``axis_names={"pp"}`` makes only the pp axis manual;
    every other mesh axis (dp/fsdp/tp/sep) stays in GSPMD "auto" mode, so the
    in-stage compute is still sharded by the usual logical-axis rules;
  - activations move between stages with ``lax.ppermute`` (compiles to
    collective-permute riding ICI);
  - the schedule is a ``lax.scan`` over ``n_micro + n_stages - 1`` ticks — the
    GPipe fill/drain pattern. Backward needs no hand-written 1F1B state machine:
    the transpose of ppermute is the reverse rotation, so ``jax.grad`` through
    the scan IS the reverse pipeline schedule. XLA's scheduler overlaps the
    collective-permute with compute (the job NCCL streams did in the reference).

Memory note: GPipe-style stashing of all microbatch activations is avoided by
``remat=True`` (per-block rematerialisation), which is how 1F1B's memory benefit
is obtained in the XLA world.
"""

from __future__ import annotations

import threading
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_state = threading.local()


def in_manual_pipeline() -> bool:
    """True while tracing inside the shard_map(pp) body.

    Layer code that opens its own shard_map (flash attention, ring attention)
    must take the plain auto-sharded path instead — nested manual meshes over
    the same axes are not composable.
    """
    return getattr(_state, "manual", False)


class _ManualCtx:
    def __enter__(self):
        self._prev = in_manual_pipeline()
        _state.manual = True

    def __exit__(self, *exc):
        _state.manual = self._prev
        return False


def gpipe_schedule(stage_fn: Callable, n_stages: int, axis_name: str = "pp",
                   with_aux: bool = False):
    """The GPipe tick schedule, to run INSIDE shard_map where ``axis_name`` is
    manual. ``stage_fn(stage_params, x, *bargs) -> y`` computes one stage
    (``-> (y, aux)`` when ``with_aux``; aux is a scalar summed over active
    ticks and psum'd over stages — MoE load-balance losses ride this).
    Returns ``pipeline(params, micro_inputs, *bargs) -> micro_outputs`` (or
    ``(micro_outputs, aux_total)``) where ``micro_inputs`` is ``[n_micro, ...]``
    (replicated over the pp axis) and the result is psum-replicated from the
    last stage.
    """
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def pipeline(params, micro_in, *bargs):
        n_micro = micro_in.shape[0]
        stage = jax.lax.axis_index(axis_name)
        total_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            buf, outs, aux_acc = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(micro_in, mb_idx, 0, keepdims=False)
            h = jnp.where(stage == 0, inject, buf)
            with _ManualCtx():
                res = stage_fn(params, h, *bargs)
            y, aux = res if with_aux else (res, None)
            if with_aux:
                # bubble ticks run on garbage activations — mask their aux
                active = (t - stage >= 0) & (t - stage < n_micro)
                aux_acc = aux_acc + jnp.where(active, aux, 0.0)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            is_out = (stage == n_stages - 1) & (t >= n_stages - 1)
            prev = jax.lax.dynamic_index_in_dim(outs, out_idx, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_out, y, prev), out_idx, 0)
            nxt = jax.lax.ppermute(y, axis_name, perm)
            return (nxt, outs, aux_acc), None

        buf0 = jnp.zeros(micro_in.shape[1:], micro_in.dtype)
        outs0 = jnp.zeros(micro_in.shape, micro_in.dtype)
        aux0 = jnp.zeros((), jnp.float32)
        (_, outs, aux_acc), _ = jax.lax.scan(
            tick, (buf0, outs0, aux0), jnp.arange(total_ticks))
        # results live on the last stage; zero elsewhere + psum replicates them
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis_name)
        if with_aux:
            return outs, jax.lax.psum(aux_acc, axis_name)
        return outs

    return pipeline


def interleaved_schedule(stage_fn: Callable, n_stages: int, interleave: int,
                         axis_name: str = "pp", with_aux: bool = False):
    """Interleaved virtual-pipeline (VPP) schedule, run INSIDE shard_map.

    Parity anchor: the reference's dygraph interleaved 1F1B
    (fleet/meta_parallel/pipeline_parallel.py:1143 PipelineParallelWithInterleave,
    pp_layers.py get_stage_from_index for the round-robin chunk placement) and
    the static VPP scheduler pass (distributed/passes/pipeline_scheduler_pass).

    TPU-native redesign: each device holds ``v = interleave`` non-adjacent layer
    chunks; every microbatch circulates the pp ring v times, one chunk-hop per
    scan tick. Device d at tick t applies its local chunk
    ``c = ((t - d) mod v*p) // p`` — a traced per-device index into the chunk-
    stacked local params — so the whole interleave is still ONE lax.scan +
    ppermute program and ``jax.grad`` through it is the reverse interleaved
    schedule. Ticks = v*M + p - 1 of chunk-size work (vs GPipe's M + p - 1 of
    stage-size work): bubble fraction drops from (p-1)/(M+p-1) to
    (p-1)/(vM+p-1) — the Megatron-interleave bubble, without a hand-written
    per-rank state machine. Requires M % p == 0 (same constraint as the
    reference: accumulate_steps % pp degree == 0).

    Zero-bubble schedules (ZBH1/ZBVPP, pipeline_scheduler_pass/__init__.py:32)
    split weight-grad from activation-grad compute to fill the drain bubble;
    that decomposition is not expressible through grad-of-scan, so it is
    implemented as a hand-built reverse schedule in :func:`zb_schedule`
    below (select with ``schedule='zb'``; composes with ``interleave`` —
    the ZBVPP shape). This function remains the grad-of-scan path.

    ``stage_fn(local_params, chunk_idx, h, *bargs)`` must apply chunk
    ``chunk_idx`` (local params carry a leading [v] chunk dim).
    """
    p, v = n_stages, interleave
    vp = v * p
    perm = [(i, (i + 1) % p) for i in range(p)]

    def pipeline(params, micro_in, *bargs):
        n_micro = micro_in.shape[0]
        d = jax.lax.axis_index(axis_name)
        total_ticks = v * n_micro + p - 1

        def tick(carry, t):
            buf, outs, aux_acc = carry
            cyc = jnp.mod(t - d, vp)
            c = jnp.clip(cyc // p, 0, v - 1)  # local chunk index this tick
            # device 0, chunk 0: inject microbatch j = (t//vp)*p + t%p
            inj_idx = jnp.clip((t // vp) * p + jnp.mod(t, vp), 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(micro_in, inj_idx, 0,
                                                  keepdims=False)
            h = jnp.where((d == 0) & (cyc < p), inject, buf)
            with _ManualCtx():
                res = stage_fn(params, c, h, *bargs)
            y, aux = res if with_aux else (res, None)
            # activity mask: entry tick e = t - (c*p + d); real microbatch iff
            # e lands in an injection window and maps to a valid index
            e = t - (c * p + d)
            er = jnp.mod(e, vp)
            mb = (e // vp) * p + er
            active = (e >= 0) & (er < p) & (mb < n_micro)
            if with_aux:
                aux_acc = aux_acc + jnp.where(active, aux, 0.0)
            # device p-1, chunk v-1: final output of microbatch mb
            is_out = (d == p - 1) & (c == v - 1) & active
            out_idx = jnp.clip(mb, 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(outs, out_idx, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_out, y, prev), out_idx, 0)
            nxt = jax.lax.ppermute(y, axis_name, perm)
            return (nxt, outs, aux_acc), None

        buf0 = jnp.zeros(micro_in.shape[1:], micro_in.dtype)
        outs0 = jnp.zeros(micro_in.shape, micro_in.dtype)
        aux0 = jnp.zeros((), jnp.float32)
        (_, outs, aux_acc), _ = jax.lax.scan(
            tick, (buf0, outs0, aux0), jnp.arange(total_ticks))
        outs = jnp.where(d == p - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis_name)
        if with_aux:
            return outs, jax.lax.psum(aux_acc, axis_name)
        return outs

    return pipeline


def zb_schedule(layer_fn, n_stages: int, interleave: int, lc: int,
                axis_name: str = "pp", bargs=(), remat: bool = False,
                with_aux: bool = False, remat_policy=None):
    """Zero-bubble (ZBH1-class) W/B-split schedule, run INSIDE shard_map.

    Parity anchor: the reference's zero-bubble pipeline passes
    (distributed/passes/pipeline_scheduler_pass/__init__.py:22,36 — ZBH1 /
    ZBVPP, impl pipeline_zero_bubble.py), which split each backward into
    activation-grad (B, on the critical path) and weight-grad (W, deferrable)
    so drain-phase bubbles fill with W work.

    TPU-native redesign (hand-built reverse schedule replacing grad-of-scan):

      1. FWD scan (ticks = vM + p - 1): identical dataflow to the interleaved
         schedule, but every LAYER of the tick's chunk runs under ``jax.vjp``;
         the per-layer pullbacks (linearization residuals) ride out of the
         scans as stacked ys — jax vjp closures are pytrees, so ``lax.scan``
         stacks them.
      2. BWD scan (reverse, same tick count): chains each layer's pullback to
         propagate ONLY the activation cotangent upstream (the weight half of
         each layer's transposed jaxpr is dead code the compiler eliminates),
         reverse-``ppermute``s it, and SAVES the per-layer output cotangents.
         Per-tick critical-path work is B only: the W third of the
         reference's bubble is GONE from both scans.
      3. W drain: one accumulation scan re-applies the saved per-layer
         pullbacks to the saved per-layer cotangents, keeping only the weight
         grads — per-layer deferral exactly like ZBH1's W ops, so no
         activation-chaining is recomputed (each layer's dW is one transpose
         given its own cotangent). No cross-stage dependency — pure local
         matmuls off the permute chain, batched per tick.

    Total critical path ≈ (vM+p-1)(F + B)/v + M·W  vs  the interleaved
    schedule's (vM+p-1)(F + B + W)/v — a saving of W·(p-1)/v wall-clock, the
    exact W-bubble ZBH1 targets.

    Memory regimes (the ZB paper's memory/bubble tradeoff axis):
      - ``remat=False`` (ZB-∞): step 1 saves full linearization residuals
        (incl. the tick's param slice) for every tick — fastest, most memory.
      - ``remat=True, remat_policy=None`` (memory-bounded, ZBH1's regime):
        step 1 saves ONLY each layer's boundary input activation; step 2
        recomputes the layer under ``jax.vjp`` w.r.t. activations only (the
        weight half is never traced); step 3 recomputes once more w.r.t.
        weights only. Memory drops to the boundary-activations class (same
        as GPipe+remat); the extra cost is one more in-layer forward in the
        W drain — which runs OFF the permute critical path, exactly where
        ZBH1 hides work.
      - ``remat=True, remat_policy=<jax.checkpoint policy>`` (selective):
        step 1 runs the vjp over the POLICY-checkpointed layer, so the
        stacked pullbacks hold only the policy-saved residuals (e.g.
        flash_out/flash_lse — backward skips re-running the flash forward
        kernel in BOTH the B scan and the W drain) plus the vjp inputs.
        Memory sits between the other two regimes: the policy-saved tensors
        AND the tick's param slice are stacked per tick (like ZB-∞); for
        models whose per-stage params dwarf activations prefer policy=None.
    Gradient equality vs sequential is exact in all regimes
    (tests/test_pipeline.py).

    ``layer_fn(per_layer_params, h, *bargs)`` runs ONE block (``-> (y,
    aux_scalar)`` when ``with_aux`` — MoE gate losses: the aux sum over
    active ticks is a second differentiable output, and its cotangent enters
    every layer pullback in both the B scan and the W drain). ``bargs`` are
    CLOSED OVER by the custom_vjp (not passed as differentiable arguments):
    rope tables etc. work unchanged, while differentiating w.r.t. a
    broadcast arg raises JAX's closed-over-tracer error at trace time
    instead of silently producing zero gradients.
    """
    p, v = n_stages, interleave
    vp = v * p
    perm_f = [(i, (i + 1) % p) for i in range(p)]
    perm_b = [(i, (i - 1) % p) for i in range(p)]
    # remat regimes: boundary (input-only storage, recompute-twice) vs
    # selective (vjp over the policy-checkpointed layer — pullbacks carry the
    # policy-saved residuals, e.g. flash out/lse, and recompute the rest)
    boundary = remat and remat_policy is None
    selective = remat and remat_policy is not None

    def _chunk(params, c):
        # chunk c's [lc, ...] slice of each local [v*lc, ...] param stack
        return [jax.lax.dynamic_slice_in_dim(w, c * lc, lc, 0)
                for w in params]

    def _fn(wl, h, *b):
        # with_aux: normalize the aux scalar to f32 INSIDE the traced fn so
        # every pullback's aux cotangent is f32 regardless of the block's
        # compute dtype (a bf16 gate under AMP would otherwise reject the
        # f32 g_aux at trace time on zb only)
        res = layer_fn(wl, h, *b)
        if with_aux:
            y, aux = res
            return y, jnp.asarray(aux, jnp.float32)
        return res

    def _meta(t, d, M):
        cyc = jnp.mod(t - d, vp)
        c = jnp.clip(cyc // p, 0, v - 1)  # local chunk index this tick
        e = t - (c * p + d)               # entry tick of this (chunk, device)
        er = jnp.mod(e, vp)
        mb_raw = (e // vp) * p + er
        active = (e >= 0) & (er < p) & (mb_raw < M)
        mb = jnp.clip(mb_raw, 0, M - 1)
        inj_here = (d == 0) & (cyc < p)   # device 0, chunk 0: consumes inject
        inj_idx = jnp.clip((t // vp) * p + jnp.mod(t, vp), 0, M - 1)
        is_out = (d == p - 1) & (c == v - 1) & active
        return c, mb, active, inj_here, inj_idx, is_out

    def _run_fwd(params, micro_in):
        M = micro_in.shape[0]
        d = jax.lax.axis_index(axis_name)
        T = v * M + p - 1

        def ftick(carry, t):
            buf, outs, aux_acc = carry
            c, mb, active, inj_here, inj_idx, is_out = _meta(t, d, M)
            inj = jax.lax.dynamic_index_in_dim(micro_in, inj_idx, 0,
                                               keepdims=False)
            h = jnp.where(inj_here, inj, buf)
            wls = _chunk(params, c)

            if boundary:
                # memory-bounded: stack each layer's INPUT activation only
                def layer_step(carry_l, wl):
                    hh, asum = carry_l
                    res = _fn(wl, hh, *bargs)
                    y, auxl = res if with_aux else (res, 0.0)
                    return (y, asum + auxl), hh
            else:
                # ZB-∞ / selective: stack the per-layer pullback (vjp
                # closures are pytrees, so lax.scan stacks their residuals).
                # Under `selective` the vjp runs over the policy-checkpointed
                # layer, so the pullback carries only policy-saved residuals
                # (flash out/lse etc.) and recomputes the rest when applied.
                vfn = (jax.checkpoint(_fn, policy=remat_policy) if selective
                       else _fn)

                def layer_step(carry_l, wl):
                    hh, asum = carry_l
                    res, pb = jax.vjp(
                        lambda w_, h_: vfn(w_, h_, *bargs), wl, hh)
                    y, auxl = res if with_aux else (res, 0.0)
                    return (y, asum + auxl), pb

            with _ManualCtx():
                (y, tick_aux), pbs_t = jax.lax.scan(
                    layer_step, (h, jnp.zeros((), jnp.float32)), wls)
            if with_aux:
                aux_acc = aux_acc + jnp.where(active, tick_aux, 0.0)
            prev = jax.lax.dynamic_index_in_dim(outs, mb, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_out, y, prev), mb, 0)
            nxt = jax.lax.ppermute(y, axis_name, perm_f)
            return (nxt, outs, aux_acc), pbs_t

        buf0 = jnp.zeros(micro_in.shape[1:], micro_in.dtype)
        outs0 = jnp.zeros(micro_in.shape, micro_in.dtype)
        aux0 = jnp.zeros((), jnp.float32)
        (_, outs, aux_acc), pbs = jax.lax.scan(
            ftick, (buf0, outs0, aux0), jnp.arange(T))
        outs = jnp.where(d == p - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis_name)
        if with_aux:
            return (outs, jax.lax.psum(aux_acc, axis_name)), pbs
        return outs, pbs

    @jax.custom_vjp
    def pipeline(params, micro_in):
        outs, _ = _run_fwd(params, micro_in)
        return outs

    def pipeline_fwd(params, micro_in):
        outs, pbs = _run_fwd(params, micro_in)
        # bargs ride the RESIDUALS: the bwd runs under a different trace than
        # the fwd whose closure captured them (shard_map transpose), so the
        # remat recomputes must read residual-plumbed values, not the closure
        return outs, (pbs, params, bargs)

    def pipeline_bwd(res, g):
        pbs, params, bargs_r = res
        if with_aux:
            g, g_aux = g
            g_aux = jax.lax.psum(jnp.asarray(g_aux, jnp.float32), axis_name)
        else:
            g_aux = None
        # mirror the transpose of the fwd's final psum: shard_map delivers a
        # replicated (P()) output's cotangent split 1/p per device; psumming
        # reconstitutes the full cotangent on every device (exactly what
        # autodiff of `psum(masked_outs)` does in the grad-of-scan schedules)
        g = jax.lax.psum(g, axis_name)
        mshape, mdtype = g.shape, g.dtype  # outs shape/dtype == micro_in's
        M = mshape[0]
        d = jax.lax.axis_index(axis_name)
        T = v * M + p - 1

        # ---- B scan: activation grads only, reverse tick order ----
        def btick(carry, xs):
            gbuf, dmicro = carry
            t, pbs_t = xs
            c, mb, active, inj_here, inj_idx, is_out = _meta(t, d, M)
            g_m = jax.lax.dynamic_index_in_dim(g, mb, 0, keepdims=False)
            dy = jnp.where(is_out, g_m.astype(gbuf.dtype), gbuf)
            dy = jnp.where(active, dy, jnp.zeros_like(dy))
            # aux cotangent: the SAME scalar reaches every active tick's
            # layers (inactive ticks' aux was masked out of the fwd sum)
            daux = (jnp.where(active, g_aux, 0.0) if with_aux else None)

            def _cot(dh):
                return (dh, daux) if with_aux else dh

            if boundary:
                # recompute the layer fwd from its saved INPUT, differentiate
                # w.r.t. activations only (weight half never traced); the
                # INCOMING dh is this layer's output cotangent — saved for W
                wls = _chunk(params, c)

                def layer_bwd(dh, xs_l):
                    hl, wl = xs_l
                    _, pb = jax.vjp(
                        lambda h_: _fn(wl, h_, *bargs_r), hl)
                    (dh2,) = pb(_cot(dh))
                    return dh2, dh

                bxs = (pbs_t, tuple(wls))
            else:
                def layer_bwd(dh, pb):
                    # weight half of pb unused here -> DCE'd from the scan
                    _dw_dead, dh2 = pb(_cot(dh))
                    return dh2, dh

                bxs = pbs_t

            dh, dys_t = jax.lax.scan(layer_bwd, dy, bxs, reverse=True)
            take = inj_here & active
            prev = jax.lax.dynamic_index_in_dim(dmicro, mb, 0, keepdims=False)
            dmicro = jax.lax.dynamic_update_index_in_dim(
                dmicro, jnp.where(take, dh, prev), mb, 0)
            # injected ticks consumed micro_in, not the permuted buf — send
            # nothing upstream for them
            send = jnp.where(inj_here, jnp.zeros_like(dh), dh)
            gnxt = jax.lax.ppermute(send, axis_name, perm_b)
            return (gnxt, dmicro), dys_t

        gbuf0 = jnp.zeros(mshape[1:], mdtype)
        dmicro0 = jnp.zeros(mshape, mdtype)
        (_, dmicro), dys = jax.lax.scan(
            btick, (gbuf0, dmicro0), (jnp.arange(T), pbs), reverse=True)
        # shard_map transposes a replicated (P()) input by psumming per-device
        # cotangents — return only THIS device's contribution
        dmicro = jnp.where(d == 0, dmicro, jnp.zeros_like(dmicro))

        # ---- W drain: per-layer weight grads from saved pullbacks + dys.
        # Iterates only the v*M ACTIVE (chunk, microbatch) pairs — bubble
        # ticks are skipped entirely (the reference's ZB schedules likewise
        # emit W ops per real microbatch only), so the drain is vM ticks of
        # pure W work vs the reverse schedules' T = vM + p - 1.
        def wtick(acc, k):
            c = k // M
            m = k - c * M
            # invert the tick mapping: entry tick of microbatch m on device 0
            # chunk 0 is (m//p)*vp + m%p; this (chunk, device) sees it c*p + d
            # ticks later
            t = (m // p) * vp + jnp.mod(m, p) + c * p + d
            pbs_t = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, t, 0,
                                                       keepdims=False), pbs)
            dys_t = jax.lax.dynamic_index_in_dim(dys, t, 0, keepdims=False)

            if boundary:
                # recompute the layer fwd once more from its saved input,
                # differentiate w.r.t. WEIGHTS only — pure local matmuls off
                # the permute chain, exactly the work ZBH1 defers
                wls = _chunk(params, c)

                def layer_w(_, xs_l):
                    hl, dyl, wl = xs_l
                    _, pb = jax.vjp(
                        lambda w_: _fn(w_, hl, *bargs_r), wl)
                    # wtick iterates only ACTIVE pairs -> aux cot = g_aux
                    (dwl,) = pb((dyl, g_aux) if with_aux else dyl)
                    return None, dwl

                wxs = (pbs_t, dys_t, tuple(wls))
            else:
                def layer_w(_, xs_l):
                    pb, dyl = xs_l
                    # activation half unused -> DCE'd
                    dwl, _dh_dead = pb((dyl, g_aux) if with_aux else dyl)
                    return None, dwl

                wxs = (pbs_t, dys_t)

            _, dws = jax.lax.scan(layer_w, None, wxs)
            # scatter-add this tick's [lc]-chunk grads into the local stack
            out = []
            for a, dch in zip(acc, dws):
                cur = jax.lax.dynamic_slice_in_dim(a, c * lc, lc, 0)
                out.append(jax.lax.dynamic_update_slice_in_dim(
                    a, cur + dch.astype(a.dtype), c * lc, 0))
            return tuple(out), None

        dw0 = tuple(jnp.zeros(a.shape, a.dtype) for a in params)
        dw, _ = jax.lax.scan(wtick, dw0, jnp.arange(v * M))
        return dw, dmicro

    pipeline.defvjp(pipeline_fwd, pipeline_bwd)
    return pipeline


def vpp_layer_order(n_layers: int, p: int, v: int):
    """Layer permutation so a contiguous [L/p] slice per device holds its v
    round-robin chunks: device d gets virtual stages {c*p + d}."""
    lc = n_layers // (v * p)
    order = []
    for d in range(p):
        for c in range(v):
            k = c * p + d
            order.extend(range(k * lc, (k + 1) * lc))
    return order


def pipeline_call(
    block_fn: Callable,
    stacked_params: Sequence[jax.Array],
    x: jax.Array,
    *broadcast_args,
    mesh: Mesh,
    n_micro: int,
    axis_name: str = "pp",
    remat: bool = False,
    with_aux: bool = False,
    interleave: int = 1,
    remat_policy=None,
    schedule: str = "auto",
):
    """Run ``x`` through ``n_layers`` stacked blocks, pipelined over ``axis_name``.

    Args:
      block_fn: ``block_fn(per_layer_params, x, *broadcast_args) -> y`` runs ONE
        block (``-> (y, aux_scalar)`` when ``with_aux`` — e.g. MoE gate losses);
        ``per_layer_params`` is a list of arrays without the stacking dim.
      stacked_params: arrays of shape ``[n_layers, ...]``; the leading dim must be
        divisible by the pp axis size (layers are assigned contiguously).
      x: global activations ``[batch, ...]``; batch must divide ``n_micro``.
      broadcast_args: extra per-call inputs replicated to every stage (e.g. rope
        tables).
      n_micro: number of microbatches (the reference's ``accumulate_steps``).
      remat: rematerialise each block in backward (fleet/recompute parity).
      schedule: "auto" (GPipe for interleave=1, interleaved VPP otherwise) or
        "zb" — the zero-bubble W/B-split schedule (see :func:`zb_schedule`;
        ``remat=True`` selects its memory-bounded boundary-storage regime
        (``remat_policy=None``) or the selective policy regime (pullbacks
        keep the policy-saved residuals, e.g. flash out/lse, skipping the
        flash fwd recompute in B and W), ``remat=False`` the ZB-∞
        residual-saving regime; ``broadcast_args``
        are non-differentiable (a grad w.r.t. one raises at trace time);
        ``with_aux`` is supported — MoE gate losses ride the zb schedule).

    Returns global activations with the same shape as ``x`` (plus the aux sum
    over all layers and microbatches when ``with_aux``).
    """
    n_stages = mesh.shape[axis_name]
    if schedule not in ("auto", "zb"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    # zb handles remat itself (boundary-storage when remat_policy is None,
    # selective policy-checkpointed pullbacks otherwise — see zb_schedule);
    # jax.checkpoint wrapping applies to the grad-of-scan schedules only.
    blk = (jax.checkpoint(block_fn, policy=remat_policy)
           if remat and schedule != "zb" else block_fn)

    def _run_layers(wls, h, *bargs):
        # wls: [n_local_layers, ...] arrays; scan blocks over the leading dim
        def body(carry, i):
            h, aux = carry
            wl = [w[i] for w in wls]
            res = blk(wl, h, *bargs)
            if with_aux:
                y, a = res
                return (y, aux + a), None
            return (res, aux), None

        (h, aux), _ = jax.lax.scan(
            body, (h, jnp.zeros((), jnp.float32)), jnp.arange(wls[0].shape[0]))
        return (h, aux) if with_aux else h

    def stage_fn(local_params, h, *bargs):
        return _run_layers(local_params, h, *bargs)

    if n_stages == 1:
        return stage_fn(list(stacked_params), x, *broadcast_args)

    batch = x.shape[0]
    if batch % n_micro != 0:
        raise ValueError(f"batch {batch} not divisible by n_micro {n_micro}")
    mb = batch // n_micro
    micro = x.reshape((n_micro, mb) + x.shape[1:])

    if interleave > 1 or schedule == "zb":
        n_layers = stacked_params[0].shape[0]
        if n_layers % (interleave * n_stages) != 0:
            raise ValueError(
                f"n_layers {n_layers} not divisible by interleave*pp "
                f"{interleave}*{n_stages}")
        if interleave > 1 and n_micro % n_stages != 0:
            raise ValueError(
                f"VPP requires n_micro % pp == 0, got {n_micro} % {n_stages} "
                f"(reference: accumulate_steps % pp_degree == 0)")
        lc = n_layers // (interleave * n_stages)

        def chunk_stage_fn(local_params, c, h, *bargs):
            # local [v*lc, ...] -> select chunk c's [lc, ...] slice
            wls = [jax.lax.dynamic_slice_in_dim(w, c * lc, lc, 0)
                   for w in local_params]
            return _run_layers(wls, h, *bargs)

    if schedule == "zb":
        def pipeline(params, micro_in, *bargs):
            # bargs are closed over by the zb custom_vjp: differentiating
            # w.r.t. them raises at trace time (vs. silent zero cotangents)
            zb = zb_schedule(blk, n_stages, interleave, lc, axis_name,
                             bargs=bargs, remat=remat, with_aux=with_aux,
                             remat_policy=remat_policy)
            return zb(params, micro_in)
    elif interleave > 1:
        pipeline = interleaved_schedule(
            chunk_stage_fn, n_stages, interleave, axis_name, with_aux=with_aux)
    else:
        pipeline = gpipe_schedule(stage_fn, n_stages, axis_name, with_aux=with_aux)
    n_params = len(stacked_params)
    out_specs = (P(), P()) if with_aux else P()
    smapped = jax.shard_map(
        pipeline,
        mesh=mesh,
        in_specs=(tuple(P(axis_name) for _ in range(n_params)), P())
        + tuple(P() for _ in broadcast_args),
        out_specs=out_specs,
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )
    res = smapped(tuple(stacked_params), micro, *broadcast_args)
    if with_aux:
        out, aux = res
        return out.reshape(x.shape), aux
    return res.reshape(x.shape)


def stack_block_params(blocks, mesh=None, axis_name: str = "pp",
                       interleave: int = 1):
    """Stack per-block parameter Tensors into ``[n_layers, ...]`` arrays.

    Returns (stacked_arrays, shardings, names, decay_mask). All blocks must have
    identical parameter structure (true for transformer decoder stacks). The
    leading dim is sharded over ``axis_name``; trailing dims follow each param's
    logical axes — so pp composes with fsdp/tp sharding of the weights
    (the reference's PP×sharding×MP hybrid, fleet/base/topology.py:70).

    With ``interleave=v > 1`` the layers are stacked in ``vpp_layer_order`` so
    each device's contiguous slice holds its v round-robin virtual-stage chunks
    (cf. pp_layers.py get_stage_from_index interleaved placement).
    """
    from jax.sharding import NamedSharding
    from .logical_sharding import logical_to_spec

    if interleave > 1 and mesh is not None:
        order = vpp_layer_order(len(blocks), mesh.shape[axis_name], interleave)
        blocks = [blocks[i] for i in order]
    else:
        order = list(range(len(blocks)))
    per_block = [[t for _, t in b.named_parameters()] for b in blocks]
    names = [n for n, _ in blocks[0].named_parameters()]
    n_params = len(per_block[0])
    for pb in per_block:
        if len(pb) != n_params:
            raise ValueError("pipeline blocks have differing parameter structure")
    frozen = [n for n, t in blocks[0].named_parameters() if t.stop_gradient]
    if frozen:
        raise NotImplementedError(
            f"pipeline blocks with frozen (stop_gradient) params not supported: {frozen}")
    stacked, shardings, decay = [], [], []
    for i in range(n_params):
        arrs = [pb[i]._data for pb in per_block]
        if mesh is not None:
            axes = getattr(per_block[0][i], "logical_axes", None) or (None,) * arrs[0].ndim
            spec = logical_to_spec((None,) + tuple(axes), mesh)
            spec = P(axis_name, *tuple(spec)[1:])
            sh = NamedSharding(mesh, spec)
            # stack under jit with out_shardings so no replicated [L, ...]
            # intermediate is ever materialised in HBM
            st = jax.jit(lambda *a: jnp.stack(a), out_shardings=sh)(*arrs)
            shardings.append(sh)
        else:
            st = jnp.stack(arrs)
            shardings.append(None)
        decay.append(arrs[0].ndim >= 2)
        stacked.append(st)
    return stacked, shardings, names, decay, order
