"""Pipeline-parallelism tests on the 8-device virtual CPU mesh.

Mirrors the reference's PP correctness strategy (test/collective/fleet/
hybrid_parallel_pp_*.py: same model trained with and without PP must match).
Here both regimes run in one process: pp-sharded mesh vs plain mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed.auto_parallel import Engine, axis_rules, make_mesh
from paddle_tpu.distributed.auto_parallel.pipeline import pipeline_call
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def _toy_block_fn(params, x):
    (w,) = params
    return jnp.tanh(x @ w)


class TestPipelineCore:
    def test_matches_sequential(self):
        mesh = make_mesh({"pp": 4, "dp": 2})
        rng = np.random.default_rng(0)
        n_layers, d = 8, 16
        ws = jnp.asarray(rng.standard_normal((n_layers, d, d)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, d)), jnp.float32)

        def loss_pp(ws, x):
            y = pipeline_call(_toy_block_fn, [ws], x, mesh=mesh, n_micro=4)
            return jnp.mean(y**2)

        def loss_seq(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, g1 = jax.jit(jax.value_and_grad(loss_pp))(ws, x)
        l2, g2 = jax.jit(jax.value_and_grad(loss_seq))(ws, x)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-6)

    def test_remat_matches(self):
        mesh = make_mesh({"pp": 2})
        rng = np.random.default_rng(1)
        ws = jnp.asarray(rng.standard_normal((4, 8, 8)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)

        def loss(remat):
            def f(ws, x):
                y = pipeline_call(_toy_block_fn, [ws], x, mesh=mesh, n_micro=2,
                                  remat=remat)
                return jnp.mean(y**2)
            return jax.jit(jax.value_and_grad(f))(ws, x)

        l1, g1 = loss(False)
        l2, g2 = loss(True)
        np.testing.assert_allclose(l1, l2, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)

    def test_interleaved_matches_sequential(self):
        """VPP (interleave=2) forward+grad == plain sequential scan."""
        from paddle_tpu.distributed.auto_parallel.pipeline import vpp_layer_order

        mesh = make_mesh({"pp": 4, "dp": 2})
        rng = np.random.default_rng(4)
        n_layers, d, v, p = 8, 16, 2, 4
        ws = jnp.asarray(rng.standard_normal((n_layers, d, d)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, d)), jnp.float32)
        order = vpp_layer_order(n_layers, p, v)
        ws_perm = ws[jnp.asarray(order)]

        def loss_vpp(wsp, x):
            y = pipeline_call(_toy_block_fn, [wsp], x, mesh=mesh, n_micro=4,
                              interleave=v)
            return jnp.mean(y**2)

        def loss_seq(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, g1p = jax.jit(jax.value_and_grad(loss_vpp))(ws_perm, x)
        l2, g2 = jax.jit(jax.value_and_grad(loss_seq))(ws, x)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        g1 = np.empty_like(np.asarray(g1p))
        g1[np.asarray(order)] = np.asarray(g1p)  # un-permute rows
        np.testing.assert_allclose(g1, np.asarray(g2), rtol=1e-4, atol=1e-6)

    def test_interleaved_rejects_bad_micro(self):
        mesh = make_mesh({"pp": 4})
        ws = jnp.zeros((8, 4, 4), jnp.float32)
        x = jnp.zeros((6, 4), jnp.float32)
        with pytest.raises(ValueError, match="n_micro % pp"):
            pipeline_call(_toy_block_fn, [ws], x, mesh=mesh, n_micro=6,
                          interleave=2)

    def test_single_stage_mesh(self):
        mesh = make_mesh({"pp": 1, "dp": 4})
        rng = np.random.default_rng(2)
        ws = jnp.asarray(rng.standard_normal((3, 8, 8)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
        y = pipeline_call(_toy_block_fn, [ws], x, mesh=mesh, n_micro=2)

        def body(h, w):
            return jnp.tanh(h @ w), None
        ref, _ = jax.lax.scan(body, x, ws)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5)


def _build_llama(seed=7, **over):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(num_hidden_layers=4, **over)
    return cfg, LlamaForCausalLM(cfg)


class TestLlamaPipelineEngine:
    def _batch(self, cfg, b=8, s=32):
        rng = np.random.default_rng(3)
        ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
        return ids

    def test_pp_loss_matches_dp(self):
        """Same seed → identical params → pp2 engine and dp engine agree on loss."""
        mesh_pp = make_mesh({"pp": 2, "dp": 2, "tp": 2})
        with axis_rules(mesh_pp):
            cfg, model_pp = _build_llama()
        eng_pp = Engine(model_pp, mesh_pp, lr=1e-2, n_micro=2)

        mesh_dp = make_mesh({"dp": 8})
        with axis_rules(mesh_dp):
            _, model_dp = _build_llama()
        eng_dp = Engine(model_dp, mesh_dp, lr=1e-2)

        ids = self._batch(cfg)
        l_pp = float(eng_pp.eval_loss(*map(jnp.asarray, (ids, ids))))
        l_dp = float(eng_dp.eval_loss(*map(jnp.asarray, (ids, ids))))
        np.testing.assert_allclose(l_pp, l_dp, rtol=2e-4)

    def test_pp_training_decreases_loss(self):
        mesh = make_mesh({"pp": 2, "fsdp": 2, "tp": 2})
        with axis_rules(mesh):
            cfg, model = _build_llama()
        eng = Engine(model, mesh, lr=5e-3, n_micro=4)
        ids = self._batch(cfg)
        ids_d, lbl_d = eng.shard_batch(ids, ids)
        l0 = float(eng.step(ids_d, lbl_d))
        for _ in range(3):
            l = float(eng.step(ids_d, lbl_d))
        assert np.isfinite(l)
        assert l < l0, f"pp training loss did not decrease: {l0} -> {l}"

    def test_pp_remat_training(self):
        mesh = make_mesh({"pp": 2})
        with axis_rules(mesh):
            cfg, model = _build_llama(recompute=True)
        eng = Engine(model, mesh, lr=5e-3, n_micro=2)
        ids = self._batch(cfg, b=4)
        ids_d, lbl_d = eng.shard_batch(ids, ids)
        l0 = float(eng.step(ids_d, lbl_d))
        l1 = float(eng.step(ids_d, lbl_d))
        assert np.isfinite(l1) and l1 < l0

    def test_vpp_engine_matches_dp_and_trains(self):
        """Engine with pp_interleave=2: loss agrees with a dp-only engine on
        identical weights, and training still converges."""
        mesh_pp = make_mesh({"pp": 2, "dp": 2})
        with axis_rules(mesh_pp):
            cfg, model_pp = _build_llama()
        eng_pp = Engine(model_pp, mesh_pp, lr=5e-3, n_micro=2, pp_interleave=2)

        mesh_dp = make_mesh({"dp": 8})
        with axis_rules(mesh_dp):
            _, model_dp = _build_llama()
        eng_dp = Engine(model_dp, mesh_dp, lr=5e-3)

        ids = self._batch(cfg)
        l_pp = float(eng_pp.eval_loss(*map(jnp.asarray, (ids, ids))))
        l_dp = float(eng_dp.eval_loss(*map(jnp.asarray, (ids, ids))))
        np.testing.assert_allclose(l_pp, l_dp, rtol=2e-4)

        ids_d, lbl_d = eng_pp.shard_batch(ids, ids)
        l0 = float(eng_pp.step(ids_d, lbl_d))
        for _ in range(3):
            l = float(eng_pp.step(ids_d, lbl_d))
        assert np.isfinite(l) and l < l0, f"VPP training: {l0} -> {l}"

    def test_vpp_sync_model_unpermutes(self):
        """sync_model must undo the VPP layer reordering."""
        mesh = make_mesh({"pp": 2, "dp": 4})
        with axis_rules(mesh):
            cfg, model = _build_llama()
        ref_first_w = None
        blocks = model.pipeline_blocks()
        name0, t0 = next(iter(blocks[0].named_parameters()))
        eng = Engine(model, mesh, lr=1e-2, n_micro=2, pp_interleave=2)
        # row r of the stack holds layer order[r]; sync writes it back to
        # blocks[order[r]] — verify against the live stacked array
        eng.sync_model()
        order = eng._pp_order
        st0 = eng.params[eng._n_rest]
        for r, li in enumerate(order):
            got = next(iter(blocks[li].named_parameters()))[1]
            np.testing.assert_allclose(np.asarray(got._data),
                                       np.asarray(st0[r]), rtol=1e-6)

    def test_sync_model_roundtrip(self):
        mesh = make_mesh({"pp": 2, "dp": 4})
        with axis_rules(mesh):
            cfg, model = _build_llama()
        eng = Engine(model, mesh, lr=1e-2, n_micro=2)
        ids = self._batch(cfg, b=4)
        ids_d, lbl_d = eng.shard_batch(ids, ids)
        eng.step(ids_d, lbl_d)
        eng.sync_model()
        # block params written back = stacked rows
        blk0 = eng._blocks[0]
        name0, t0 = next(iter(blk0.named_parameters()))
        np.testing.assert_allclose(
            np.asarray(t0._data), np.asarray(eng.params[eng._n_rest][0]), rtol=1e-6)


class TestZeroBubble:
    """ZBH1-class W/B-split schedule (pipeline.zb_schedule).

    Reference: distributed/passes/pipeline_scheduler_pass/__init__.py:22,36
    (ZBH1/ZBVPP) — grads must equal sequential exactly, like the GPipe/VPP
    tests above.
    """

    def test_zb_matches_sequential(self):
        mesh = make_mesh({"pp": 4, "dp": 2})
        rng = np.random.default_rng(10)
        ws = jnp.asarray(rng.standard_normal((8, 16, 16)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)

        def loss_zb(ws, x):
            y = pipeline_call(_toy_block_fn, [ws], x, mesh=mesh, n_micro=4,
                              schedule="zb")
            return jnp.mean(y**2)

        def loss_seq(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, (gw1, gx1) = jax.jit(jax.value_and_grad(loss_zb, argnums=(0, 1)))(ws, x)
        l2, (gw2, gx2) = jax.jit(jax.value_and_grad(loss_seq, argnums=(0, 1)))(ws, x)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                                   rtol=1e-4, atol=1e-6)

    def test_zb_interleaved_matches_sequential(self):
        """ZBVPP-class: W/B split composed with interleave=2."""
        from paddle_tpu.distributed.auto_parallel.pipeline import vpp_layer_order

        mesh = make_mesh({"pp": 4, "dp": 2})
        rng = np.random.default_rng(11)
        n_layers, d, v, p = 8, 16, 2, 4
        ws = jnp.asarray(rng.standard_normal((n_layers, d, d)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, d)), jnp.float32)
        order = vpp_layer_order(n_layers, p, v)
        ws_perm = ws[jnp.asarray(order)]

        def loss_zb(wsp, x):
            y = pipeline_call(_toy_block_fn, [wsp], x, mesh=mesh, n_micro=4,
                              schedule="zb", interleave=v)
            return jnp.mean(y**2)

        def loss_seq(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, g1p = jax.jit(jax.value_and_grad(loss_zb))(ws_perm, x)
        l2, g2 = jax.jit(jax.value_and_grad(loss_seq))(ws, x)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        g1 = np.empty_like(np.asarray(g1p))
        g1[np.asarray(order)] = np.asarray(g1p)
        np.testing.assert_allclose(g1, np.asarray(g2), rtol=1e-4, atol=1e-6)

    def test_zb_broadcast_args_nondiff_ok_diff_raises(self):
        """bargs are closed over by the zb custom_vjp: a non-differentiated
        barg (rope tables etc.) works and matches sequential; differentiating
        w.r.t. one raises loudly instead of returning silent zeros
        (ADVICE r3, pipeline.py zb bargs)."""
        mesh = make_mesh({"pp": 4})
        rng = np.random.default_rng(12)
        ws = jnp.asarray(rng.standard_normal((8, 16, 16)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        scale = jnp.float32(1.1)

        def blk(params, h, s):
            (w,) = params
            return jnp.tanh(h @ w) * s

        def loss_zb(ws, x, s):
            y = pipeline_call(blk, [ws], x, s, mesh=mesh, n_micro=4,
                              schedule="zb")
            return jnp.mean(y**2)

        def loss_seq(ws, x, s):
            def body(h, w):
                return jnp.tanh(h @ w) * s, None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, g1 = jax.jit(jax.value_and_grad(loss_zb))(ws, x, scale)
        l2, g2 = jax.jit(jax.value_and_grad(loss_seq))(ws, x, scale)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-6)
        with pytest.raises(jax.errors.UnexpectedTracerError):
            jax.jit(jax.grad(loss_zb, argnums=2))(ws, x, scale)

    def test_zb_with_aux_matches_sequential(self):
        """MoE gate losses ride the zb schedule (round 4 — was a
        NotImplementedError): the aux side-output is differentiable and
        grads equal the sequential per-microbatch computation, in both
        memory regimes."""
        mesh = make_mesh({"pp": 4})
        rng = np.random.default_rng(15)
        ws = jnp.asarray(rng.standard_normal((8, 16, 16)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)

        def blk(params, h):
            (w,) = params
            y = jnp.tanh(h @ w)
            return y, (y ** 2).mean()

        def loss_seq(ws, x):
            mb = x.reshape(4, 2, 16)

            def run_mb(h):
                def body(c, w):
                    h, a = c
                    y = jnp.tanh(h @ w)
                    return (y, a + (y ** 2).mean()), None
                (y, a), _ = jax.lax.scan(body, (h, 0.0), ws)
                return y, a

            ys, auxs = jax.vmap(run_mb)(mb)
            return jnp.mean(ys.reshape(8, 16) ** 2) + 0.1 * auxs.sum()

        from paddle_tpu.distributed.auto_parallel.pipeline import \
            vpp_layer_order

        l2, g2 = jax.jit(jax.value_and_grad(loss_seq))(ws, x)
        for remat in (False, True):
            for v in (1, 2):  # zb and ZBVPP composition
                wsp = ws
                if v > 1:
                    order = vpp_layer_order(8, 4, v)
                    wsp = ws[jnp.asarray(order)]

                def loss_zb(wsp, x, remat=remat, v=v):
                    y, aux = pipeline_call(blk, [wsp], x, mesh=mesh,
                                           n_micro=4, schedule="zb",
                                           with_aux=True, remat=remat,
                                           interleave=v)
                    return jnp.mean(y ** 2) + 0.1 * aux

                l1, g1 = jax.jit(jax.value_and_grad(loss_zb))(wsp, x)
                np.testing.assert_allclose(l1, l2, rtol=1e-5)
                g1n = np.asarray(g1)
                if v > 1:
                    out = np.empty_like(g1n)
                    out[np.asarray(order)] = g1n
                    g1n = out
                np.testing.assert_allclose(g1n, np.asarray(g2),
                                           rtol=1e-4, atol=1e-6)

    def test_zb_selective_remat_policy_matches_sequential(self):
        """zb + remat=True + a selective remat_policy (round 5 — previously
        the policy was ignored with a warning): the vjp runs over the
        policy-checkpointed layer, so pullbacks carry the policy-saved
        residuals and grads still equal sequential exactly."""
        from jax.ad_checkpoint import checkpoint_name

        mesh = make_mesh({"pp": 4})
        rng = np.random.default_rng(16)
        ws = jnp.asarray(rng.standard_normal((8, 16, 16)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)

        def blk(params, h):
            (w,) = params
            # the named intermediate plays the role of flash_out: the policy
            # saves it, everything else is recomputed in the pullback
            a = checkpoint_name(jnp.tanh(h @ w), "blk_act")
            return a + 0.1 * h

        policy = jax.checkpoint_policies.save_only_these_names("blk_act")

        def loss_zb(ws, x):
            y = pipeline_call(blk, [ws], x, mesh=mesh, n_micro=4,
                              schedule="zb", remat=True, remat_policy=policy)
            return jnp.mean(y**2)

        def loss_seq(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w) + 0.1 * h, None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, (gw1, gx1) = jax.jit(
            jax.value_and_grad(loss_zb, argnums=(0, 1)))(ws, x)
        l2, (gw2, gx2) = jax.jit(
            jax.value_and_grad(loss_seq, argnums=(0, 1)))(ws, x)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                                   rtol=1e-4, atol=1e-6)

    def test_zb_engine_matches_dp_and_trains(self):
        """Engine(pp_schedule='zb'): loss agrees with dp-only on identical
        weights; training converges."""
        mesh_pp = make_mesh({"pp": 2, "dp": 2})
        with axis_rules(mesh_pp):
            cfg, model_pp = _build_llama()
        eng_pp = Engine(model_pp, mesh_pp, lr=5e-3, n_micro=2,
                        pp_schedule="zb")

        mesh_dp = make_mesh({"dp": 8})
        with axis_rules(mesh_dp):
            _, model_dp = _build_llama()
        eng_dp = Engine(model_dp, mesh_dp, lr=5e-3)

        ids = self._batch(cfg)
        l_pp = float(eng_pp.eval_loss(*map(jnp.asarray, (ids, ids))))
        l_dp = float(eng_dp.eval_loss(*map(jnp.asarray, (ids, ids))))
        np.testing.assert_allclose(l_pp, l_dp, rtol=2e-4)

        ids_d, lbl_d = eng_pp.shard_batch(ids, ids)
        l0 = float(eng_pp.step(ids_d, lbl_d))
        for _ in range(3):
            l = float(eng_pp.step(ids_d, lbl_d))
        assert np.isfinite(l) and l < l0, f"ZB training: {l0} -> {l}"

    _batch = TestLlamaPipelineEngine._batch

    def test_zb_step_equals_vpp_step_llama(self):
        """ZB and VPP produce the same training trajectory on identically
        seeded llama models — the grads (through clip+AdamW) must agree."""
        mesh = make_mesh({"pp": 2, "dp": 2})

        def run(schedule, interleave):
            with axis_rules(mesh):
                cfg, model = _build_llama()
            eng = Engine(model, mesh, lr=5e-3, n_micro=2,
                         pp_schedule=schedule, pp_interleave=interleave)
            ids = self._batch(cfg, b=4)
            ids_d, lbl_d = eng.shard_batch(ids, ids)
            return [float(eng.step(ids_d, lbl_d)) for _ in range(3)]

        zb = run("zb", 1)
        vpp = run("auto", 2)
        np.testing.assert_allclose(zb, vpp, rtol=2e-4)


class TestZeroBubbleRemat:
    """Memory-bounded (ZBH1-regime) zero-bubble: boundary-activation storage
    + inside-layer recompute in B and W (VERDICT r3 next #4). Grads must
    stay exactly sequential, and the schedule must compose with
    Engine(pp_remat=True)."""

    def test_zb_remat_matches_sequential(self):
        mesh = make_mesh({"pp": 4})
        rng = np.random.default_rng(13)
        ws = jnp.asarray(rng.standard_normal((8, 16, 16)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)

        def loss_zb(ws, x):
            y = pipeline_call(_toy_block_fn, [ws], x, mesh=mesh, n_micro=4,
                              schedule="zb", remat=True)
            return jnp.mean(y**2)

        def loss_seq(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, (gw1, gx1) = jax.jit(
            jax.value_and_grad(loss_zb, argnums=(0, 1)))(ws, x)
        l2, (gw2, gx2) = jax.jit(
            jax.value_and_grad(loss_seq, argnums=(0, 1)))(ws, x)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                                   rtol=1e-4, atol=1e-6)

    def test_zb_remat_interleaved_matches_sequential(self):
        from paddle_tpu.distributed.auto_parallel.pipeline import vpp_layer_order

        mesh = make_mesh({"pp": 4})
        rng = np.random.default_rng(14)
        n_layers, d, v, p = 8, 16, 2, 4
        ws = jnp.asarray(rng.standard_normal((n_layers, d, d)), jnp.float32) * 0.5
        x = jnp.asarray(rng.standard_normal((8, d)), jnp.float32)
        order = vpp_layer_order(n_layers, p, v)
        ws_perm = ws[jnp.asarray(order)]

        def loss_zb(wsp, x):
            y = pipeline_call(_toy_block_fn, [wsp], x, mesh=mesh, n_micro=4,
                              schedule="zb", remat=True, interleave=v)
            return jnp.mean(y**2)

        def loss_seq(ws, x):
            def body(h, w):
                return jnp.tanh(h @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.mean(y**2)

        l1, g1p = jax.jit(jax.value_and_grad(loss_zb))(ws_perm, x)
        l2, g2 = jax.jit(jax.value_and_grad(loss_seq))(ws, x)
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
        g1 = np.empty_like(np.asarray(g1p))
        g1[np.asarray(order)] = np.asarray(g1p)
        np.testing.assert_allclose(g1, np.asarray(g2), rtol=1e-4, atol=1e-6)

    def test_zb_remat_engine_llama(self):
        """Engine(pp_schedule='zb', recompute=True model): loss agrees with
        the dp-only engine and training decreases the loss — zb now composes
        with exactly the memory-constrained configs that need it."""
        import paddle_tpu as paddle

        mesh_pp = make_mesh({"pp": 2, "dp": 2})
        paddle.seed(7)
        with axis_rules(mesh_pp):
            cfg = LlamaConfig.tiny(num_hidden_layers=4, recompute=True)
            model_pp = LlamaForCausalLM(cfg)
        eng_pp = Engine(model_pp, mesh_pp, lr=5e-3, n_micro=2,
                        pp_schedule="zb")
        assert eng_pp._pp_remat  # model recompute flag flows to the schedule

        mesh_dp = make_mesh({"dp": 8})
        paddle.seed(7)
        with axis_rules(mesh_dp):
            model_dp = LlamaForCausalLM(
                LlamaConfig.tiny(num_hidden_layers=4, recompute=True))
        eng_dp = Engine(model_dp, mesh_dp, lr=5e-3)

        rng = np.random.default_rng(3)
        ids = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
        l_pp = float(eng_pp.eval_loss(*map(jnp.asarray, (ids, ids))))
        l_dp = float(eng_dp.eval_loss(*map(jnp.asarray, (ids, ids))))
        np.testing.assert_allclose(l_pp, l_dp, rtol=2e-4)

        ids_d, lbl_d = eng_pp.shard_batch(ids, ids)
        l0 = float(eng_pp.step(ids_d, lbl_d))
        for _ in range(3):
            l = float(eng_pp.step(ids_d, lbl_d))
        assert np.isfinite(l) and l < l0, f"zb+remat training: {l0} -> {l}"

    def test_zb_engine_moe_llama_trains(self):
        """Engine(pp_schedule='zb') on a MoE llama: the gate aux loss rides
        the zb schedule (round 4 — previously NotImplementedError) and
        training decreases the loss."""
        import paddle_tpu as paddle

        mesh = make_mesh({"pp": 2, "dp": 2})
        paddle.seed(9)
        with axis_rules(mesh):
            cfg = LlamaConfig.tiny(num_hidden_layers=2, num_experts=4)
            model = LlamaForCausalLM(cfg)
        assert model.pipeline_with_aux
        eng = Engine(model, mesh, lr=5e-3, n_micro=2, pp_schedule="zb")
        rng = np.random.default_rng(3)
        ids = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
        ids_d, lbl_d = eng.shard_batch(ids, ids)
        l0 = float(eng.step(ids_d, lbl_d))
        for _ in range(3):
            l = float(eng.step(ids_d, lbl_d))
        assert np.isfinite(l) and l < l0, (l0, l)
