"""BERT / ViT / UNet model-family tests: forward shapes, loss, training step
(reference model: hybrid_strategy + dygraph model tests run tiny configs)."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (BertConfig, BertForMaskedLM,
                               BertForSequenceClassification,
                               UNet2DConditionModel, UNetConfig)
from paddle_tpu.vision.models import ViTConfig, VisionTransformer


class TestBert:
    def test_forward_shapes(self):
        cfg = BertConfig.tiny()
        model = BertForMaskedLM(cfg)
        ids = np.random.randint(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        logits = model(paddle.to_tensor(ids))
        assert tuple(np.asarray(logits._data if hasattr(logits, "_data")
                                else logits).shape) == (2, 16, cfg.vocab_size)

    def test_mlm_loss_and_masking(self):
        cfg = BertConfig.tiny()
        model = BertForMaskedLM(cfg)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        labels = np.full((2, 16), -100, np.int64)
        labels[:, :4] = ids[:, :4]  # only 4 positions scored
        loss = model.loss_fn(ids, labels)
        lv = float(loss._data if hasattr(loss, "_data") else loss)
        assert np.isfinite(lv)
        assert abs(lv - np.log(cfg.vocab_size)) < 1.5  # ~chance at init

    def test_mlm_trains(self):
        paddle.seed(0)
        cfg = BertConfig.tiny(num_hidden_layers=1)
        model = BertForMaskedLM(cfg)
        rng = np.random.default_rng(1)
        ids = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
        lbl = ids.astype(np.int64)
        opt = paddle.optimizer.AdamW(learning_rate=2e-3,
                                     parameters=list(model.parameters()))
        from paddle_tpu.jit.api import _collect_state, _Swap
        import jax

        names, tensors = _collect_state(model)

        @jax.jit
        def loss_and_grad(arrs):
            def f(a):
                with _Swap(tensors, a):
                    return model.loss_fn(ids, lbl)
            return jax.value_and_grad(f)(arrs)

        first = None
        for _ in range(8):
            arrs = [t._data for t in tensors]
            loss, grads = loss_and_grad(arrs)
            for t, g in zip(tensors, grads):
                if not t.stop_gradient:
                    t._grad = paddle.Tensor(g)
            opt.step()
            opt.clear_grad()
            if first is None:
                first = float(loss)
        assert float(loss) < first

    def test_sequence_classification(self):
        cfg = BertConfig.tiny(num_labels=3)
        model = BertForSequenceClassification(cfg)
        ids = np.random.randint(0, cfg.vocab_size, (2, 8)).astype(np.int32)
        logits = model(paddle.to_tensor(ids))
        arr = logits._data if hasattr(logits, "_data") else logits
        assert tuple(np.asarray(arr).shape) == (2, 3)
        loss = model.loss_fn(ids, np.array([0, 2], np.int64))
        assert np.isfinite(float(loss._data if hasattr(loss, "_data") else loss))


class TestViT:
    def test_forward_and_loss(self):
        cfg = ViTConfig.tiny()
        model = VisionTransformer(cfg)
        imgs = np.random.rand(2, 3, 32, 32).astype(np.float32)
        logits = model(paddle.to_tensor(imgs))
        arr = np.asarray(logits._data if hasattr(logits, "_data") else logits)
        assert arr.shape == (2, 10)
        loss = model.loss_fn(imgs, np.array([1, 7], np.int64))
        lv = float(loss._data if hasattr(loss, "_data") else loss)
        assert abs(lv - np.log(10)) < 1.0

    def test_factories(self):
        from paddle_tpu.vision.models import vit_b_16

        model = vit_b_16(image_size=32, patch_size=16, num_classes=5)
        assert model.config.hidden_size == 768


class TestUNet:
    def test_forward_shape_and_loss(self):
        cfg = UNetConfig.tiny()
        model = UNet2DConditionModel(cfg)
        rng = np.random.default_rng(0)
        sample = rng.standard_normal((2, 4, 16, 16)).astype(np.float32)
        t = np.array([10, 500], np.int32)
        ctx = rng.standard_normal((2, 6, cfg.cross_attention_dim)).astype(np.float32)
        out = model(paddle.to_tensor(sample), paddle.to_tensor(t),
                    paddle.to_tensor(ctx))
        arr = np.asarray(out._data if hasattr(out, "_data") else out)
        assert arr.shape == (2, 4, 16, 16)
        noise = rng.standard_normal(sample.shape).astype(np.float32)
        loss = model.loss_fn({"sample": sample, "timesteps": t,
                              "context": ctx, "noise": noise})
        assert np.isfinite(float(loss))

    @pytest.mark.slow   # full UNet backward on CPU ~17s; forward/loss and
    #                     the bf16-parity test keep fast UNet coverage
    def test_grad_flows_through_unet(self):
        import jax

        cfg = UNetConfig.tiny()
        model = UNet2DConditionModel(cfg)
        from paddle_tpu.jit.api import _collect_state, _Swap

        _, tensors = _collect_state(model)
        rng = np.random.default_rng(1)
        batch = {
            "sample": rng.standard_normal((1, 4, 16, 16)).astype(np.float32),
            "timesteps": np.array([3], np.int32),
            "context": rng.standard_normal((1, 4, cfg.cross_attention_dim)).astype(np.float32),
            "noise": rng.standard_normal((1, 4, 16, 16)).astype(np.float32),
        }

        def f(arrs):
            with _Swap(tensors, arrs):
                return model.loss_fn(batch)

        loss, grads = jax.value_and_grad(f)([t._data for t in tensors])
        assert np.isfinite(float(loss))
        gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in grads)
        assert gnorm > 0


@pytest.mark.slow   # two full UNet forward+backward compiles, 70-115 s on CPU
#                     (it was a standing failure through PR 20, passes on jax
#                     0.9, and alone then costs an eighth of the 870 s budget);
#                     TestUNet.test_forward_shape_and_loss stays the fast pin
def test_unet_bf16_matches_fp32():
    """bf16 params/activations (round 4): loss within bf16 tolerance of the
    fp32 model on identical weights, grads finite — the bench's SD-UNet
    line runs this dtype."""
    import jax
    import jax.numpy as jnp

    paddle.seed(0)
    m16 = UNet2DConditionModel(UNetConfig.tiny(dtype="bfloat16"))
    paddle.seed(0)
    m32 = UNet2DConditionModel(UNetConfig.tiny())
    rng = np.random.default_rng(0)
    batch = {
        "sample": rng.standard_normal((2, 4, 16, 16)).astype(np.float32),
        "timesteps": np.array([10, 500], np.int32),
        "context": rng.standard_normal((2, 6, 32)).astype(np.float32),
        "noise": rng.standard_normal((2, 4, 16, 16)).astype(np.float32),
    }
    l16, l32 = float(m16.loss_fn(batch)), float(m32.loss_fn(batch))
    assert abs(l16 - l32) / l32 < 0.05, (l16, l32)

    from paddle_tpu.jit.api import _collect_state, _Swap

    _, tensors = _collect_state(m16)

    def f(arrs):
        with _Swap(tensors, arrs):
            return m16.loss_fn(batch)

    _, grads = jax.value_and_grad(f)([t._data for t in tensors])
    assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in grads)
