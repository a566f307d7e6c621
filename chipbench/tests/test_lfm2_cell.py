"""What PR 28 added for ``lfm2-24b-a2b.chat-batch-64``: the family's weights
out of its leaf table, ``ops/grouped_matmul.py`` against hand counts, each
new reader on a trace written by hand or on made-up counters (``None`` where
there is nothing to read), and the cell's rehearsal."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench.harness import loader
from chipbench.harness import trace as trace_lib
from chipbench.harness import weights as W
from chipbench.metrics import _program
from chipbench.ops import grouped_matmul as gmm

CELL = "lfm2-24b-a2b.chat-batch-64"
HERE = os.path.dirname(os.path.abspath(__file__))


def _helpers():
    spec = importlib.util.spec_from_file_location(
        "_chipbench_test_program", os.path.join(HERE, "test_program.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name):
    return loader._module("metrics", name, name)


# ---- the family's weights ------------------------------------------------------

def test_weights_layer_by_layer_are_the_whole_models_bits():
    import jax.numpy as jnp

    cell = loader.load(CELL, rehearse=True)
    table = cell.leaf_table
    kinds = [tuple(n for n, _, _ in leaves) for leaves in table["layers"]]
    assert kinds[0][:4] == ("op_norm", "w_in", "conv_k", "w_out")
    assert "w1" in kinds[0] and "router" not in kinds[0]       # the dense one
    assert kinds[1][:7] == ("op_norm", "wq", "wk", "wv", "wo", "q_gain",
                            "k_gain")
    assert {"router", "expert_bias", "experts_w1"} <= set(kinds[1])
    assert [n for n, _, _ in table["top"]] == ["embed", "final_norm"]
    seed = 2 ** 31 + 77
    whole = W.model_weights(table, seed, dtype=jnp.float32)
    for i in range(len(table["layers"])):
        alone = W.layer_weights(table, seed, i)
        assert set(alone) == set(whole["layers"][i])
        for k, v in alone.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(whole["layers"][i][k]))
    top = W.top_weights(table, seed)
    for k, v in top.items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(whole[k]))
    bias = np.asarray(whole["layers"][1]["expert_bias"])
    assert bias.ndim == 1 and abs(bias.mean()) < 0.2 < 10 * bias.std()


def test_positions_all_but_a_tie_give_no_gap(monkeypatch):
    """``hidden_states_many`` states each position's route margin beside its
    state; ``token_stats`` gives the two numbers read as maxima only where
    it is ``ROUTE_MARGIN`` or more, and the number read as a mean
    everywhere; the states themselves are the dense family's arithmetic."""
    import jax.numpy as jnp

    from chipbench.reference import decoder

    cell = loader.load(CELL, rehearse=True)
    ref, cfg, seed = cell.reference, cell.config, 2 ** 31 + 5
    top = W.top_weights(cell.leaf_table, seed)
    ids = np.random.default_rng(3).integers(
        0, cfg["vocab_size"], (1, 40)).astype(np.int32)
    x = ref.hidden_states_many(
        cfg, [ids], lambda i: W.layer_weights(cell.leaf_table, seed, i),
        top)[0][0]
    h = cfg["hidden_size"]
    assert x.shape == (40, h + 1)
    margin = np.asarray(x[:, h])
    assert np.isfinite(margin).all() and (margin > 0).all()
    monkeypatch.setattr(ref, "ROUTE_MARGIN", float(np.median(margin)))
    pos = np.arange(8, 40, dtype=np.int32)
    tok = np.zeros(32, np.int32)
    got = ref.token_stats(cfg, x, pos, tok, top, 0.7, 0.95)
    want = decoder.token_stats(
        {"rms_norm_eps": cfg["norm_eps"]}, x[:, :h], pos, tok,
        {"final_norm": top["final_norm"], "head": top["embed"].T}, 0.7, 0.95)
    tie = margin[pos] < np.median(margin)
    assert 4 < tie.sum() < 28
    np.testing.assert_array_equal(np.asarray(got["best_gap"])[tie], 0.0)
    assert np.all(np.asarray(got["nucleus_gap"])[tie] == -np.inf)
    for name in ("best_gap", "nucleus_gap"):
        np.testing.assert_array_equal(np.asarray(got[name])[~tie],
                                      np.asarray(want[name])[~tie])
    assert (np.asarray(got["best_gap"])[~tie] > 0).all()
    for name in ("mass_above", "mass_above_expected"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))
    np.testing.assert_array_equal(
        np.asarray(ref.logits_of(cfg, x, top)),
        np.asarray(ref.logits_of(cfg, x[:, :h], top)))
    first, _ = ref.draw_tokens(cfg, x, pos, top, 0.7, 0.95,
                               W.seed_key(seed))
    np.testing.assert_array_equal(
        np.asarray(first),
        np.asarray(jnp.argmax(ref.logits_of(cfg, x, top)[pos], -1)))


def test_the_reference_rounds_what_it_is_handed_to_the_served_values():
    """Float32 weights that are not bfloat16 values (what the chip hands
    over: its compiler drops ``astype(bfloat16).astype(float32)``) give the
    states of their bfloat16 roundings; rounded ones pass unchanged."""
    import jax.numpy as jnp

    cell = loader.load(CELL, rehearse=True)
    ref, cfg, seed = cell.reference, cell.config, 2 ** 31 + 9
    rounded = lambda t: {k: v.astype(jnp.bfloat16).astype(jnp.float32)
                         for k, v in t.items()}
    off = lambda t: {k: v * (1 + 2.0 ** -11) for k, v in t.items()}
    top = W.top_weights(cell.leaf_table, seed)
    layer = lambda i: W.layer_weights(cell.leaf_table, seed, i)
    for k, v in ref._served(off(top)).items():
        np.testing.assert_array_equal(np.asarray(v), np.asarray(
            rounded(off(top))[k]))
        np.testing.assert_array_equal(np.asarray(ref._served(top)[k]),
                                      np.asarray(top[k]))
    ids = np.random.default_rng(4).integers(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    got = ref.hidden_states_many(cfg, [ids], lambda i: off(layer(i)),
                                 off(top))[0]
    want = ref.hidden_states_many(cfg, [ids],
                                  lambda i: rounded(off(layer(i))),
                                  rounded(off(top)))[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_published_widths_and_the_three_cuts():
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b-cut"]
    with open(os.path.join(loader.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 9 == len(cfg["layer_types"])
    assert cfg["layer_types"].count("full_attention") == 2
    assert cfg["num_dense_layers"] == 1


# ---- operations and bytes --------------------------------------------------------

def test_grouped_matmul_counts_by_hand():
    # 256 rows over 63 visited experts of 2048 x 1536, bf16, no tiles
    w = 63 * 2048 * 1536 * 2
    assert gmm.grouped_matmul_bytes(256, 63, 2048, 1536) == w + 256 * (
        2048 + 1536) * 2
    assert gmm.grouped_matmul_flops(256, 63, 2048, 1536) == 2 * 256 * 2048 \
        * 1536
    # tiles of 16: every visited expert computes a tile at least
    assert gmm.padded_rows(256, 63, 16) == 63 * 16
    assert gmm.padded_rows(4096 * 4, 64, 128) == 4096 * 4
    assert gmm.grouped_matmul_flops(256, 63, 2048, 1536, 16) == 2 * 1008 \
        * 2048 * 1536
    # a routed SwiGLU layer: two up, one down
    assert gmm.expert_ffn_bytes(256, 64, 2048, 1536) == 3 * 64 * 2048 * 1536 \
        * 2 + 3 * 256 * (2048 + 1536) * 2
    assert gmm.expert_ffn_flops(256, 64, 2048, 1536) == 6 * 256 * 2048 * 1536
    # the whole of a token step's experts at 819 GB/s: 8 layers, 11.8 ms
    step = 8 * gmm.expert_ffn_bytes(256, 64, 2048, 1536) / 819e9
    assert 0.0115 < step < 0.0120


# ---- the counter readers -----------------------------------------------------------

def _run(stats0, stats1, cell=CELL):
    c = loader.load(cell)
    return types.SimpleNamespace(
        cell=c, trace=None, device={"peaks": {"hbm_bytes_per_s": 819e9}},
        window={"stats0": stats0, "stats1": stats1})


def test_counter_readers_on_made_up_counters():
    s0 = {"moe_experts_touched": 100, "moe_layer_steps": 10,
          "moe_rows_routed": 2560, "moe_rows_max_expert": 90}
    s1 = {"moe_experts_touched": 100 + 8 * 60, "moe_layer_steps": 18,
          "moe_rows_routed": 2560 + 8 * 256, "moe_rows_max_expert": 90 + 8 * 9}
    run = _run(s0, s1)
    assert _reader("moe_experts_touched_share").read(run) == pytest.approx(
        100 * 60 / 64)
    assert _reader("moe_load_max_over_mean").read(run) == pytest.approx(
        9 / 4)


@pytest.mark.parametrize("metric", [
    "moe_experts_touched_share", "moe_load_max_over_mean",
    "decode_block_step_ms", "moe_gmm_roofline", "moe_share", "conv_share",
    "attn_share"])
def test_readers_find_nothing_on_a_program_without_the_names(metric):
    """The parent's engine has none of the counters, and a run without a
    trace has no scopes: nothing to read, nothing raised."""
    old = {"steps": 5, "hit_tokens": 9}
    assert _reader(metric).read(_run(old, dict(old, steps=9))) is None
    zero = {k: 0 for k in ("moe_experts_touched", "moe_layer_steps",
                           "moe_rows_routed", "moe_rows_max_expert")}
    assert _reader(metric).read(_run(zero, dict(zero))) is None


# ---- the trace readers, on a trace written by hand -----------------------------------

def _block_text(h, named=True):
    """One decode block of two token steps, 0-20 ms: per step (10 ms) the
    experts' matmuls 4 ms and the router 1 ms under pt.moe, the conv 1 ms
    with its ring update 0.5 ms, the attention layers' gather 1.5 ms and
    append 0.5 ms, the sampler 1.5 ms."""
    MS = h.MS
    blk = "jit(pt_decode_block)/while/body/"
    sc = (lambda s: s) if named else (lambda s: "")
    metas = {
        1: ("%while.1 = (s32[]) while(...)", "jit(pt_decode_block)/while"),
        2: ("%fusion.2 = bf16[64,64,1536]",
            blk + sc("pt.moe/pt.moe.experts/") + "dot_general:"),
        3: ("%fusion.3 = f32[64,64]",
            blk + sc("pt.moe/pt.moe.router/") + "dot_general:"),
        4: ("%fusion.4 = bf16[64,2048]", blk + sc("pt.conv/") + "mul:"),
        5: ("%scatter.5 = bf16[10497,3,2048]",
            blk + sc("pt.conv/pt.state_write/") + "scatter:"),
        6: ("%fusion.6 = f32[64,8,4,2560]", blk + sc("pt.attn/") + "gather:"),
        7: ("%scatter.7 = bf16[10497,8,16,64]",
            blk + sc("pt.attn/pt.kv_write/") + "scatter:"),
        8: ("%sort.8 = f32[64,65536]", blk + sc("pt.sampler/") + "sort:"),
        9: (("jit_pt_decode_block" if named else "jit_run") + "(7)", ""),
    }
    ops = [h._event(1, 0, 20 * MS)]
    for s in (0, 10):
        t = s * MS
        for meta, dur in ((2, 4.0), (3, 1.0), (4, 1.0), (5, 0.5), (6, 1.5),
                          (7, 0.5), (8, 1.5)):
            ops.append(h._event(meta, int(t), int(dur * MS)))
            t += dur * MS
    dev = h._plane(1, "/device:TPU:0", {
        "XLA Modules": [h._event(9, 0, 20 * MS)], "XLA Ops": ops},
        metas, {1: "tf_op"})
    host = h._plane(2, "/host:CPU", {"python3": [
        h._event(1, 0, 21 * MS)]}, {1: ("bench.engine.step", "")}, {})
    return dev + "\n" + host


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    h = _helpers()
    monkeypatch.setattr(_program, "ROOT", str(tmp_path))
    _program._CACHE.clear()
    out = {}
    for name, named in (("named", True), ("unnamed", False)):
        path = h._write(str(tmp_path), name, _block_text(h, named))
        s0 = {k: 0 for k in ("moe_experts_touched", "moe_layer_steps",
                             "moe_rows_routed")}
        s1 = {"moe_experts_touched": 8 * 2 * 63, "moe_layer_steps": 8 * 2,
              "moe_rows_routed": 8 * 2 * 256}
        run = _run(s0, s1)
        run.cell = types.SimpleNamespace(name=name, config=run.cell.config)
        run.trace = trace_lib.reduce(path)
        out[name] = run
    return out


def test_scope_readers_known_answers(traced):
    run = traced["named"]
    assert _reader("moe_share").read(run) == pytest.approx(100 * 5 / 10)
    assert _reader("conv_share").read(run) == pytest.approx(100 * 1.5 / 10)
    assert _reader("attn_share").read(run) == pytest.approx(100 * 2 / 10)
    # 8 ms of experts' matmuls for 2 steps (two sorts of the sampler) x 8
    # layers over 63 experts
    need = 2 * 8 * gmm.expert_ffn_bytes(256, 63, 2048, 1536) / 819e9
    assert _reader("moe_gmm_roofline").read(run) == pytest.approx(
        100 * need / 8e-3)
    # the block's 20 ms over its two token steps
    assert _reader("decode_block_step_ms").read(run) == pytest.approx(10.0)
    for metric in ("moe_share", "conv_share", "attn_share",
                   "moe_gmm_roofline", "decode_block_step_ms"):
        assert _reader(metric).read(traced["unnamed"]) is None


# ---- the cell's rehearsal --------------------------------------------------------------

def test_rehearsal_is_correct_and_never_a_result():
    p = subprocess.run(
        [sys.executable, os.path.join(loader.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 2828), "--seconds", "8", "--trace",
         "1", "--rehearse"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    assert p.returncode == 3, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # the counters' readers read on the CPU too; the trace's need a chip
    for name in ("moe_experts_touched_share", "moe_load_max_over_mean",
                 "prefix_hit_share"):
        assert name in line["metrics"], name
