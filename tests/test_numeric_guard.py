"""Numeric guard tests: the on-device health word (guard_step), guarded
Engine skip semantics (moments bit-identical, step counter advances),
GuardPolicy escalation + LR re-warm, rollback determinism against an
uninterrupted run, AmpScaler's aggregated overflow check, the
check_numerics / TensorCheckerConfig wiring, bad-batch capture, and the
DataLoader worker-death / skip-corrupt policies (PT-DATA-001/002).

The end-to-end seeded drills (nan_grad / loss_spike / poison_batch, each
flipping the exit code with recovery off) run in tools/fault_drill.py,
gated by tests/test_ci_gates.py::test_fault_drill_matrix.
"""

import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.auto_parallel import Engine
from paddle_tpu.distributed.resilience import (
    FaultPlan,
    FaultSpec,
    NumericWatchdog,
    ResilientTrainer,
)
from paddle_tpu.framework import numeric_guard as ng
from paddle_tpu.framework.numeric_guard import (
    BadBatchRecorder,
    GuardPolicy,
    NumericAnomalyError,
)

D = 8


class Toy(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = paddle.nn.Linear(D, D)

    def loss_fn(self, x, y):
        out = self.fc(Tensor(x))
        diff = out._data - y
        return (diff * diff).mean()


def _data_fn(step, b=8):
    rng = np.random.default_rng(1000 + step)
    return (rng.standard_normal((b, D)).astype(np.float32),
            rng.standard_normal((b, D)).astype(np.float32))


def _engine(policy):
    paddle.seed(0)
    return Engine(Toy(), None, lr=0.05, clip_norm=None, guard=policy)


def _builder(policy):
    def build(alive):
        return _engine(policy)

    return build


# ---------------------------------------------------------------------------
# guard_step — the pure on-device combinator
# ---------------------------------------------------------------------------

class TestGuardStep:
    def _run(self, loss, grads, state=None, **kw):
        state = ng.guard_init_state() if state is None else state
        word, s2 = ng.guard_step(jnp.float32(loss),
                                 [jnp.asarray(g) for g in grads], state, **kw)
        return int(word), s2

    def test_healthy_word_is_zero_and_scalar(self):
        state = ng.guard_init_state()
        word, s2 = jax.jit(ng.guard_step)(jnp.float32(1.0),
                                          [jnp.ones((4, 4))], state)
        assert word.shape == () and word.dtype == jnp.int32
        assert int(word) == 0
        assert np.asarray(s2)[2] == 1          # healthy step counted

    def test_nan_and_inf_grad_bits(self):
        w, _ = self._run(1.0, [np.array([np.nan, 1.0], np.float32)])
        assert w == ng.NAN_GRAD
        w, _ = self._run(1.0, [np.ones(3, np.float32),
                               np.array([np.inf], np.float32)])
        assert w == ng.INF_GRAD
        assert ng.health_codes(w) == ["PT-NUM-002"]

    def test_nan_loss_bit(self):
        w, _ = self._run(np.nan, [np.ones(3, np.float32)])
        assert w & ng.NAN_LOSS
        assert "PT-NUM-003" in ng.describe_health(w)

    def test_spike_after_warmup_only(self):
        state = ng.guard_init_state()
        for _ in range(4):                     # flat loss 1.0, warm the EMA
            w, state = self._run(1.0, [np.ones(2, np.float32)], state,
                                 warmup_steps=3)
            assert w == 0
        w, state = self._run(100.0, [np.ones(2, np.float32)], state,
                             warmup_steps=3)
        assert w == ng.SPIKE
        # the anomalous loss must NOT have moved the detector state
        w2, _ = self._run(1.0, [np.ones(2, np.float32)], state,
                          warmup_steps=3)
        assert w2 == 0

    def test_spike_before_warmup_ignored(self):
        state = ng.guard_init_state()
        w, state = self._run(1.0, [np.ones(2, np.float32)], state)
        w, _ = self._run(1000.0, [np.ones(2, np.float32)], state)
        assert w == 0                          # n=1 < warmup default 5

    def test_bf16_grads_supported(self):
        g = jnp.array([np.inf], jnp.bfloat16)
        w, _ = self._run(1.0, [g])
        assert w == ng.INF_GRAD


# ---------------------------------------------------------------------------
# guarded Engine — skip semantics inside the jitted step
# ---------------------------------------------------------------------------

class TestEngineGuard:
    def test_skip_preserves_params_and_moments_bit_identical(self):
        # ONE fixed batch for every healthy step: its loss only falls, so
        # the spike bit cannot fire. Fresh batches move the loss by ~20%
        # against a 10% band after a 2-step warmup — whether step 4 then
        # read healthy depended on the init's random stream (it stopped
        # under jax 0.9's PRNG defaults: loss 1.91 vs ema 1.51, word 8).
        eng = _engine(GuardPolicy(action="skip_step", warmup_steps=2))
        for s in range(3):
            eng.step(*_data_fn(0))
        p0 = [np.asarray(a) for a in eng.params]
        m0 = [np.asarray(a) for a in eng.m]
        v0 = [np.asarray(a) for a in eng.v]
        x, y = _data_fn(0)
        x[0, 0] = np.nan                       # poisoned batch -> NaN grads
        eng.step(x, y)
        word = int(eng.last_health)
        assert word & ng.NAN_GRAD and word & ng.NAN_LOSS
        assert all(np.array_equal(a, np.asarray(b))
                   for a, b in zip(p0, eng.params))
        assert all(np.array_equal(a, np.asarray(b)) for a, b in zip(m0, eng.m))
        assert all(np.array_equal(a, np.asarray(b)) for a, b in zip(v0, eng.v))
        assert int(eng.step_count) == 4        # counter advances on a skip
        # and the next healthy step trains normally
        loss = eng.step(*_data_fn(0))
        assert np.isfinite(float(loss)) and int(eng.last_health) == 0

    def test_warn_policy_applies_the_update(self):
        eng = _engine(GuardPolicy(action="warn", warmup_steps=2))
        eng.step(*_data_fn(0))
        x, y = _data_fn(1)
        x[:] = np.nan
        eng.step(x, y)
        assert int(eng.last_health) != 0
        # skip_mask==0: the anomalous update went through (params now NaN)
        assert any(np.isnan(np.asarray(p)).any() for p in eng.params)

    def test_injection_codes_are_traced_not_retraced(self):
        """nan_grad injection arrives as a scalar arg — the same compiled
        step serves faulted and clean steps (guard criterion: no retrace,
        no per-tensor host sync added by injection)."""
        eng = _engine(GuardPolicy(action="skip_step", warmup_steps=2))
        plan = FaultPlan(seed=1, specs=[
            FaultSpec("numeric.step", "nan_grad", at=1, count=1)])
        with plan:
            eng.step(*_data_fn(0))
            compiled = eng._jit_step
            eng.step(*_data_fn(1))             # fault fires here
            assert int(eng.last_health) & ng.NAN_GRAD
            eng.step(*_data_fn(2))
        assert eng._jit_step is compiled
        assert int(eng.last_health) == 0

    def test_guard_rejects_pluggable_optimizer(self):
        paddle.seed(0)
        model = Toy()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        with pytest.raises(ValueError, match="built-in AdamW"):
            Engine(model, None, optimizer=opt, guard=GuardPolicy())


# ---------------------------------------------------------------------------
# GuardPolicy / NumericWatchdog — escalation and LR re-warm
# ---------------------------------------------------------------------------

class TestWatchdog:
    def test_skip_budget_escalates_to_rollback(self):
        wd = NumericWatchdog(GuardPolicy(action="skip_step",
                                         max_skips_per_window=2, window=10))
        assert wd.observe(1, 0) == "ok"
        assert wd.observe(2, ng.NAN_GRAD) == "skip_step"
        assert wd.observe(3, ng.NAN_GRAD) == "skip_step"
        assert wd.observe(4, ng.NAN_GRAD) == "rollback"

    def test_window_prunes_old_skips(self):
        wd = NumericWatchdog(GuardPolicy(action="skip_step",
                                         max_skips_per_window=2, window=5))
        assert wd.observe(1, ng.SPIKE) == "skip_step"
        assert wd.observe(2, ng.SPIKE) == "skip_step"
        # step 20: both prior skips fell out of the 5-step window
        assert wd.observe(20, ng.SPIKE) == "skip_step"

    def test_rollback_budget_exhaustion_aborts(self):
        wd = NumericWatchdog(GuardPolicy(action="rollback", max_rollbacks=1))
        assert wd.observe(5, ng.SPIKE) == "rollback"
        wd.note_rollback(4)
        assert wd.observe(7, ng.SPIKE) == "abort"

    def test_abort_policy_and_error_codes(self):
        wd = NumericWatchdog(GuardPolicy(action="abort"))
        assert wd.observe(3, ng.NAN_LOSS) == "abort"
        err = NumericAnomalyError(ng.NAN_LOSS | ng.SPIKE, step=3)
        assert err.codes == ["PT-NUM-003", "PT-NUM-004"]
        assert "step 3" in str(err)

    def test_lr_rewarm_ramp(self):
        wd = NumericWatchdog(GuardPolicy(action="rollback", rewarm_steps=4))
        assert wd.lr_scale(10) == 1.0          # no rollback yet
        wd.note_rollback(10)
        assert wd.lr_scale(10) == pytest.approx(0.25)
        assert wd.lr_scale(11) == pytest.approx(0.5)
        assert wd.lr_scale(13) == pytest.approx(1.0)
        assert wd.lr_scale(14) == 1.0          # ramp disarmed

    def test_warn_policy_warns(self):
        wd = NumericWatchdog(GuardPolicy(action="warn"))
        with pytest.warns(UserWarning, match="PT-NUM-001"):
            assert wd.observe(2, ng.NAN_GRAD) == "warn"


# ---------------------------------------------------------------------------
# rollback determinism — trajectory matches the uninterrupted seeded run
# ---------------------------------------------------------------------------

class TestRollbackDeterminism:
    def test_nan_grad_rollback_matches_uninterrupted(self, tmp_path):
        """Inject nan_grad at step K under ROLLBACK: restore the ring
        entry, deterministically re-seed (the builder re-runs), replay —
        the post-rollback trajectory must match a run that never saw the
        fault (mirrors the PR-2 heartbeat-loss drill)."""
        pol = GuardPolicy(action="rollback", warmup_steps=3,
                          spike_factor=50.0)
        ref = ResilientTrainer(_builder(pol), str(tmp_path / "ref"),
                               save_every=100, async_save=False
                               ).fit(_data_fn, 8)
        plan = FaultPlan(seed=3, specs=[
            FaultSpec("numeric.step", "nan_grad", at=5, count=1)])
        trainer = ResilientTrainer(_builder(pol), str(tmp_path / "job"),
                                   save_every=2, async_save=False)
        with plan:
            out = trainer.fit(_data_fn, 8)
        assert out["numeric_rollbacks"] == 1
        assert out["rollback_at"] == [4]       # anomaly at 6 -> ring entry 4
        assert out["numeric_events"][0][1] & ng.NAN_GRAD
        for s in range(5, 9):                  # replayed tail matches exactly
            assert np.allclose(out["losses"][s], ref["losses"][s], rtol=1e-4)

    def test_skip_policy_records_and_continues(self, tmp_path):
        pol = GuardPolicy(action="skip_step", warmup_steps=3,
                          spike_factor=50.0)
        plan = FaultPlan(seed=3, specs=[
            FaultSpec("data.batch", "poison_batch", at=2, count=1, arg=4)])
        trainer = ResilientTrainer(_builder(pol), str(tmp_path),
                                   save_every=100, async_save=False)
        with plan:
            out = trainer.fit(_data_fn, 6)
        assert out["numeric_skips"] == [3]
        assert np.isfinite(out["losses"][6])
        rec = BadBatchRecorder(str(tmp_path / "badbatch"))
        assert rec.steps() == [3]
        meta, arrays = rec.load(3)
        assert meta["codes"] and "input_ids" in arrays
        assert np.isnan(arrays["input_ids"]).any() or \
            np.isnan(arrays["labels"]).any()

    def test_abort_policy_raises_typed_error(self, tmp_path):
        pol = GuardPolicy(action="abort", warmup_steps=3)
        plan = FaultPlan(seed=3, specs=[
            FaultSpec("numeric.step", "nan_grad", at=2, count=1)])
        trainer = ResilientTrainer(_builder(pol), str(tmp_path),
                                   save_every=100, async_save=False)
        with plan, pytest.raises(NumericAnomalyError) as ei:
            trainer.fit(_data_fn, 6)
        assert "PT-NUM-001" in ei.value.codes


# ---------------------------------------------------------------------------
# AmpScaler — aggregated overflow check, skip-step semantics
# ---------------------------------------------------------------------------

class TestAmpScalerSkip:
    def _fit_one(self, scaler, opt, model, poison=False):
        x = Tensor(np.ones((4, D), np.float32))
        y = Tensor(np.zeros((4, D), np.float32))
        out = model.fc(x)
        loss = ((out - y) * (out - y)).mean()
        scaled = scaler.scale(loss)
        scaled.backward()
        if poison:                             # overflow: inf grad
            p = opt._parameter_list[0]
            p.grad._data = jnp.full_like(p.grad._data, jnp.inf)
        scaler.step(opt)
        scaler.update()

    def test_skipped_step_moments_bit_identical_and_scale_shrinks(self):
        from paddle_tpu.amp import GradScaler

        paddle.seed(0)
        model = Toy()
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        scaler = GradScaler(init_loss_scaling=1024.0)
        self._fit_one(scaler, opt, model)      # healthy step: moments exist
        moments = {name: {pid: np.asarray(a) for pid, a in d.items()}
                   for name, d in opt._accumulators.items()}
        params = [np.asarray(p._data) for p in opt._parameter_list]
        scale0 = scaler._scale
        ng.consume_health()
        self._fit_one(scaler, opt, model, poison=True)
        assert scaler._found_inf
        # the optimizer step was skipped: moments and params bit-identical
        for name, d in opt._accumulators.items():
            for pid, a in d.items():
                assert np.array_equal(moments[name][pid], np.asarray(a)), name
        for before, p in zip(params, opt._parameter_list):
            assert np.array_equal(before, np.asarray(p._data))
        # dynamic loss scaling shrank
        assert scaler._scale == pytest.approx(scale0 * 0.5)
        # and the overflow reported into the shared health word (PT-NUM-005)
        word = ng.consume_health()
        assert word & ng.OVERFLOW

    def test_healthy_step_records_no_overflow(self):
        from paddle_tpu.amp import GradScaler

        paddle.seed(0)
        model = Toy()
        opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                     parameters=model.parameters())
        scaler = GradScaler(init_loss_scaling=2.0)
        ng.consume_health()
        self._fit_one(scaler, opt, model)
        assert not scaler._found_inf
        assert ng.consume_health() & ng.OVERFLOW == 0


# ---------------------------------------------------------------------------
# check_numerics + TensorCheckerConfig -> health word
# ---------------------------------------------------------------------------

class TestTensorChecker:
    def teardown_method(self, _m):
        from paddle_tpu.amp.debugging import disable_tensor_checker

        disable_tensor_checker()
        ng.consume_health()

    def test_abort_mode_raises_naming_the_op(self):
        from paddle_tpu.amp.debugging import (DebugMode, TensorCheckerConfig,
                                              enable_tensor_checker)

        enable_tensor_checker(TensorCheckerConfig(
            debug_mode=DebugMode.CHECK_NAN_INF_AND_ABORT))
        ng.consume_health()
        with pytest.raises(FloatingPointError, match="log"):
            paddle.log(paddle.to_tensor(np.float32([-1.0])))
        assert ng.consume_health() & ng.NAN_GRAD

    def test_warn_mode_warns_and_records(self):
        from paddle_tpu.amp.debugging import (DebugMode, TensorCheckerConfig,
                                              enable_tensor_checker)

        enable_tensor_checker(TensorCheckerConfig(
            debug_mode=DebugMode.CHECK_NAN_INF))
        ng.consume_health()
        with pytest.warns(UserWarning, match="log"):
            t = paddle.log(paddle.to_tensor(np.float32([-1.0])))
        assert np.isnan(t.numpy()).any()       # warn mode keeps going
        assert ng.consume_health() & ng.NAN_GRAD

    def test_check_numerics_explicit_modes(self):
        from paddle_tpu.amp.debugging import DebugMode, check_numerics

        bad = paddle.to_tensor(np.float32([np.nan, np.inf]))
        with pytest.raises(FloatingPointError, match="op=mul var=x"):
            check_numerics(bad, op_type="mul", var_name="x",
                           debug_mode=DebugMode.CHECK_NAN_INF_AND_ABORT)
        with pytest.warns(UserWarning):
            n_nan, n_inf = check_numerics(
                bad, op_type="mul", var_name="x",
                debug_mode=DebugMode.CHECK_NAN_INF)
        assert int(n_nan.numpy()) == 1 and int(n_inf.numpy()) == 1
        word = ng.consume_health()
        assert word & ng.NAN_GRAD and word & ng.INF_GRAD

    def test_disable_restores_silence(self):
        from paddle_tpu.amp.debugging import (disable_tensor_checker,
                                              enable_tensor_checker)

        enable_tensor_checker()
        disable_tensor_checker()
        paddle.log(paddle.to_tensor(np.float32([-1.0])))  # no raise


# ---------------------------------------------------------------------------
# DataLoader robustness — PT-DATA-001 / PT-DATA-002
# ---------------------------------------------------------------------------

class _FlakyDataset(paddle.io.Dataset):
    """__getitem__ raises on the poisoned indices."""

    def __init__(self, n=16, bad=()):
        self.n = n
        self.bad = set(bad)

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i in self.bad:
            raise ValueError(f"corrupt record {i}")
        return np.full((4,), i, np.float32)


class _DieOnceDataset(paddle.io.Dataset):
    """Kills its worker process the first time the marked index is read;
    after the flag file exists the retry succeeds (a transient crash)."""

    def __init__(self, flag_path, n=8, die_at=3, always=False):
        self.flag = flag_path
        self.n = n
        self.die_at = die_at
        self.always = always

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.die_at and (self.always or not os.path.exists(self.flag)):
            if not self.always:
                open(self.flag, "w").close()
            os._exit(3)                        # hard death, no cleanup
        return np.full((4,), i, np.float32)


class TestDataLoaderRobustness:
    def test_skip_corrupt_single_process(self):
        dl = paddle.io.DataLoader(_FlakyDataset(8, bad=[2, 3]), batch_size=2,
                                  skip_corrupt=True)
        with pytest.warns(UserWarning, match="PT-DATA-002"):
            batches = list(dl)
        # batch [2,3] vanished entirely; others intact
        assert len(batches) == 3
        seen = sorted(float(v) for b in batches for v in b.numpy()[:, 0])
        assert seen == [0.0, 1.0, 4.0, 5.0, 6.0, 7.0]

    def test_corrupt_sample_without_policy_raises(self):
        dl = paddle.io.DataLoader(_FlakyDataset(8, bad=[2]), batch_size=2)
        with pytest.raises(ValueError, match="corrupt record 2"):
            list(dl)

    def test_skip_corrupt_multiprocess(self):
        dl = paddle.io.DataLoader(_FlakyDataset(16, bad=[4, 5]),
                                  batch_size=2, num_workers=2,
                                  skip_corrupt=True, use_shared_memory=False)
        batches = list(dl)
        assert len(batches) == 7               # batch [4,5] skipped
        seen = sorted(float(v) for b in batches for v in b.numpy()[:, 0])
        assert seen == [float(i) for i in range(16) if i not in (4, 5)]

    def test_corrupt_sample_multiprocess_raises_without_policy(self):
        dl = paddle.io.DataLoader(_FlakyDataset(8, bad=[2]), batch_size=2,
                                  num_workers=2, use_shared_memory=False)
        with pytest.raises(RuntimeError, match="corrupt record 2"):
            list(dl)

    def test_worker_death_respawns_once(self, tmp_path):
        ds = _DieOnceDataset(str(tmp_path / "died"), n=8, die_at=3)
        dl = paddle.io.DataLoader(ds, batch_size=2, num_workers=2,
                                  use_shared_memory=False)
        batches = list(dl)                     # must not wedge
        assert os.path.exists(tmp_path / "died")  # the death happened
        assert len(batches) == 4
        seen = sorted(float(v) for b in batches for v in b.numpy()[:, 0])
        assert seen == [float(i) for i in range(8)]

    def test_worker_death_budget_exhausted_typed_error(self, tmp_path):
        from paddle_tpu.io import DataLoaderWorkerError

        ds = _DieOnceDataset(str(tmp_path / "died"), n=8, die_at=3,
                             always=True)
        dl = paddle.io.DataLoader(ds, batch_size=2, num_workers=2,
                                  use_shared_memory=False,
                                  worker_respawn_limit=1)
        with pytest.raises(DataLoaderWorkerError, match="PT-DATA-001"):
            list(dl)
