"""The sigmoid, bias-corrected gate and the dropless routed FFN
(incubate/distributed/models/moe): the choice and the gates, nothing dropped,
both dispatch arms, and the share test of the model-configs guide's section 4
(a layer told which experts it holds gives its part; the parts sum to the
whole)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import (DroplessMoE,
                                                        SigmoidGate,
                                                        dropless_ffn)
from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
    _DENSE_ROWS as DENSE_ROWS, dropless_arm)

D, F = 32, 16


def _layer(experts=64, k=4, first=0, count=None, seed=3):
    paddle.seed(seed)
    gate = SigmoidGate(D, experts, topk=k, initializer_range=0.5)
    gate.expert_bias._data = 0.05 * jax.random.normal(
        jax.random.key(seed), (experts,), jnp.float32)
    paddle.seed(seed + 1)
    return DroplessMoE(D, experts, F, gate, first=first, count=count,
                       initializer_range=0.3)


def _plain(layer, x):
    """The layer's mathematics, token by token, in numpy float64."""
    g = layer.gate
    w = np.asarray(g.gate_weight._data, np.float64)
    b = np.asarray(g.expert_bias._data, np.float64)
    e = layer.experts
    wg, wu, wd = (np.asarray(a._data, np.float64)
                  for a in (e.w_gate, e.w_up, e.w_down))
    out = np.zeros((len(x), D))
    x = np.asarray(x, np.float64)
    for i, t in enumerate(x):
        s = 1.0 / (1.0 + np.exp(-(t @ w)))
        pick = np.argsort(-(s + b), kind="stable")[:g.top_k]
        gates = s[pick] / (s[pick].sum() + g.norm_eps)
        for ex, gt in zip(pick, gates):
            j = ex - layer.first
            if 0 <= j < e.num_experts:
                h = t @ wg[j]
                out[i] += gt * ((h / (1 + np.exp(-h)) * (t @ wu[j])) @ wd[j])
    return out


def _x(n, seed=0):
    return np.random.default_rng(seed).standard_normal((n, D)).astype(
        np.float32)


def test_gate_scores_choice_and_gates():
    """s = sigmoid(x W) in float32; the choice is by s + b, the gates are s
    at the chosen (the bias is not in them), renormalised with the 1e-6."""
    layer = _layer(experts=8, k=3)
    g = layer.gate
    x = _x(50)
    idx, gates = g.route(jnp.asarray(x, jnp.bfloat16))
    assert gates.dtype == jnp.float32 and idx.dtype == jnp.int32
    xb = np.asarray(jnp.asarray(x, jnp.bfloat16), np.float64)
    s = 1 / (1 + np.exp(-(xb @ np.asarray(g.gate_weight._data, np.float64))))
    b = np.asarray(g.expert_bias._data, np.float64)
    want = np.argsort(-(s + b), -1, kind="stable")[:, :3]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want, -1)).all()
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(gates), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
        rtol=2e-6)
    # the bias moves the choice: without it some tokens choose otherwise
    plain = np.argsort(-s, -1, kind="stable")[:, :3]
    assert (np.sort(plain, -1) != np.sort(want, -1)).any()


def test_arm_follows_from_the_row_count():
    assert dropless_arm(64) == dropless_arm(DENSE_ROWS) == "dense"
    assert dropless_arm(512) == dropless_arm(4096) == "sorted"


@pytest.mark.parametrize("rows", [24, DENSE_ROWS + 44],
                         ids=["dense-arm", "sorted-arm"])
def test_layer_equals_the_plain_mathematics(rows):
    layer = _layer()
    x = _x(rows)
    got, counted = layer(x, with_rows=True)
    np.testing.assert_allclose(np.asarray(got), _plain(layer, x),
                               atol=2e-4, rtol=1e-4)
    idx, _ = layer.gate.route(x)
    np.testing.assert_array_equal(
        np.asarray(counted), np.bincount(np.asarray(idx).reshape(-1),
                                         minlength=64))
    assert int(counted.sum()) == rows * 4


@pytest.mark.parametrize("rows", [40, DENSE_ROWS + 60],
                         ids=["dense-arm", "sorted-arm"])
def test_every_token_to_one_expert_nothing_dropped(rows):
    """A capacity would drop all but a few of them: here every token's pick
    is computed and the result is the plain one."""
    layer = _layer(experts=16, k=1)
    layer.gate.expert_bias._data = jnp.zeros(16).at[5].set(10.0)
    x = _x(rows, 1)
    got, counted = layer(x, with_rows=True)
    assert int(counted[5]) == rows and int(counted.sum()) == rows
    want = _plain(layer, x)
    assert np.abs(want).sum(-1).min() > 0          # no token came out empty
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("rows", [40, DENSE_ROWS + 60],
                         ids=["dense-arm", "sorted-arm"])
def test_eight_shares_sum_to_the_uncut_layer(rows):
    """The share test: told it holds experts [8i, 8i+8), the layer routes
    over all 64 and computes its own experts' part; the eight parts sum to
    the layer that holds all 64."""
    whole = _layer()
    x = _x(rows, 2)
    want = np.asarray(whole(x))
    parts, counted = [], []
    for i in range(8):
        share = _layer(first=8 * i, count=8)
        share.gate.gate_weight._data = whole.gate.gate_weight._data
        share.gate.expert_bias._data = whole.gate.expert_bias._data
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share.experts, name)._data = getattr(
                whole.experts, name)._data[8 * i: 8 * i + 8]
        y, r = share(x, with_rows=True)
        parts.append(np.asarray(y))
        counted.append(np.asarray(r))
    assert all(np.abs(p).max() > 0 for p in parts)
    np.testing.assert_allclose(sum(parts), want, atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(want, _plain(whole, x), atol=2e-4, rtol=1e-4)
    assert int(np.concatenate(counted).sum()) == rows * 4


def test_the_two_arms_agree():
    """One layer, the same tokens through the dense arm (a few rows a call)
    and the sorted arm (all at once)."""
    layer = _layer()
    x = _x(DENSE_ROWS + 64, 4)
    idx, gates = layer.gate.route(x)
    assert dropless_arm(len(x)) == "sorted"
    whole, _ = dropless_ffn(jnp.asarray(x), idx, gates, layer.experts)
    piece, _ = dropless_ffn(jnp.asarray(x[:50]), idx[:50], gates[:50],
                            layer.experts)
    np.testing.assert_allclose(np.asarray(whole)[:50], np.asarray(piece),
                               atol=2e-4, rtol=1e-4)


def test_bad_expert_range_is_refused():
    gate = SigmoidGate(D, 8, topk=2)
    with pytest.raises(ValueError, match="are not among"):
        DroplessMoE(D, 8, F, gate, first=6, count=4)
