"""What PR 36 added for ``nemotron-3-nano-30b-a3b.chat-short-batch-32``: the
configuration's published widths and its two cuts, the family's weights out
of its leaf table, ``ops/ssd.py`` against hand counts, the reference's route
margin on a made-up router, each new reader on a trace written by hand or on
made-up counters (``None`` where there is nothing to read), and the cell's
rehearsal."""

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
from chipbench.ops import ssd

CELL = "nemotron-3-nano-30b-a3b.chat-short-batch-32"
HERE = os.path.dirname(os.path.abspath(__file__))


def _helpers():
    spec = importlib.util.spec_from_file_location(
        "_chipbench_test_program", os.path.join(HERE, "test_program.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name):
    return loader._module("metrics", name, name)


# ---- the configuration ------------------------------------------------------------

def test_published_widths_and_the_two_cuts():
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [c for c in bench["configs"]
              if c["name"] == "nemotron-3-nano-30b-a3b-ep8"]
    with open(os.path.join(loader.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "routed_scaling_factor": 2.5, "ssm_state_size": 128,
        "tie_word_embeddings": False, "topk_group": 1, "use_conv_bias": True}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "n_routed_experts", "vocab_size"]
    assert (cfg["n_routed_experts"], cfg["vocab_size"]) == (16, 16384)
    assert cfg["published"] == {"n_routed_experts": 128,
                                "vocab_size": 131072}
    pattern = cfg["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
        23, 23, 6)
    # the cut's arithmetic: 5.26 B parameters, 10.51 GB of bf16
    table = loader.load(CELL).leaf_table
    n = sum(int(np.prod(shape)) for leaves in (table["top"],)
            + tuple(table["layers"]) for _, shape, _ in leaves)
    assert 5.25e9 < n < 5.27e9


def test_weights_layer_by_layer_are_the_whole_models_bits():
    import jax.numpy as jnp

    cell = loader.load(CELL, rehearse=True)
    table = cell.leaf_table
    kinds = [tuple(n for n, _, _ in leaves) for leaves in table["layers"]]
    assert kinds[0] == ("norm", "w_in", "conv_w", "conv_b", "dt_bias",
                        "A_log", "D", "ssm_norm", "w_out")
    assert kinds[1] == ("norm", "router", "e_score_correction_bias",
                        "experts_up", "experts_down", "shared_up",
                        "shared_down")
    assert kinds[3] == ("norm", "wq", "wk", "wv", "wo")
    by_kind = {n: k for leaves in table["layers"] for n, _, k in leaves}
    assert {by_kind[n] for n in ("A_log", "dt_bias", "D", "conv_b",
                                 "e_score_correction_bias")} == {"normal"}
    assert {by_kind[n] for n in ("norm", "ssm_norm")} == {"gain"}
    # the router keeps the published width, the experts are those held
    shapes = {n: s for n, s, _ in table["layers"][1]}
    assert shapes["router"][1] == 8 and shapes["experts_up"][0] == 4
    seed = 2 ** 31 + 77
    whole = W.model_weights(table, seed, dtype=jnp.float32)
    for i in range(len(table["layers"])):
        alone = W.layer_weights(table, seed, i)
        assert set(alone) == set(whole["layers"][i])
        for k, v in alone.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(whole["layers"][i][k]))


# ---- the route margin on a made-up router ----------------------------------------------

def test_the_margin_counts_only_pairs_that_involve_a_held_expert():
    """Eight experts, three a token, experts [0, 2) held; a router that is
    the identity, so ``s + bias`` is set by hand through the input."""
    import jax.numpy as jnp

    from chipbench.reference import nemotron_h as ref

    w = {"router": jnp.eye(8, dtype=jnp.float32),
         "e_score_correction_bias": jnp.zeros(8, jnp.float32)}
    logit = lambda s: np.log(np.asarray(s) / (1 - np.asarray(s)))
    s = np.array([
        # chosen 2, 3, 4 (0.9 0.8 0.7), first left out 5 (0.69): a near-tie
        # between two absent experts; the held ones lie far below
        [0.1, 0.2, 0.9, 0.8, 0.7, 0.69, 0.3, 0.25],
        # held expert 0 is the last chosen (0.7) against absent 5 (0.69)
        [0.7, 0.2, 0.9, 0.8, 0.1, 0.69, 0.3, 0.25],
        # held expert 1 is the first left out (0.69) against absent 4 (0.7)
        [0.1, 0.69, 0.9, 0.8, 0.7, 0.3, 0.3, 0.25],
        # held 0 chosen high (0.95), held 1 far below: the near-tie 4 / 5
        # involves neither; 0 against the first left out (0.69) is the
        # least pair with a held expert
        [0.95, 0.2, 0.9, 0.1, 0.7, 0.69, 0.3, 0.25]], np.float64)
    idx, g, margin = ref.route(w, jnp.asarray(logit(s), jnp.float32), k=3,
                               renorm=True, scaling=2.5, first=0, held=2)
    assert [sorted(r) for r in np.asarray(idx)] == [
        [2, 3, 4], [0, 2, 3], [2, 3, 4], [0, 2, 4]]
    np.testing.assert_allclose(
        np.asarray(margin), [0.7 - 0.2, 0.7 - 0.69, 0.7 - 0.69, 0.95 - 0.69],
        atol=1e-5)
    # with every expert held the margin is the plain one
    _, _, plain = ref.route(w, jnp.asarray(logit(s), jnp.float32), k=3,
                            renorm=True, scaling=2.5, first=0, held=8)
    np.testing.assert_allclose(np.asarray(plain), [0.01] * 4, atol=1e-5)
    # a share that holds none of a position's experts near the boundary
    _, _, none = ref.route(w, jnp.asarray(logit(s[:1]), jnp.float32), k=3,
                           renorm=True, scaling=2.5, first=6, held=2)
    np.testing.assert_allclose(np.asarray(none), [0.7 - 0.3], atol=1e-5)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 2.5, rtol=1e-5)


def test_mamba_leaves_ride_the_familys_initialisation():
    """What the model holds of a Mamba-2 layer's seeded leaves: ``A`` from 1
    to 16 and ``dt`` from 0.001 to 0.1 with the drawn leaf added, the taps
    times 4, exactly so in bfloat16; everything else as drawn."""
    import jax.numpy as jnp

    c = loader.load(CELL)
    ref, cfg = c.reference, c.config
    a_log, dt_bias = ref.family_init(cfg)
    assert a_log.shape == dt_bias.shape == (64,)
    assert np.allclose(np.exp(a_log)[[0, -1]], [1, 16])
    assert np.allclose(np.log1p(np.exp(dt_bias))[[0, -1]], [1e-3, 0.1],
                       rtol=1e-4)
    assert ref.conv_scale(cfg) == 4
    w = W.layer_weights(c.leaf_table, 2 ** 31 + 5, 0)
    held = ref.on_family_init(cfg, w)
    assert set(held) == set(w)
    np.testing.assert_array_equal(held["A_log"], a_log + np.asarray(w["A_log"]))
    taps = np.asarray(held["conv_w"])
    np.testing.assert_array_equal(taps, 4 * np.asarray(w["conv_w"]))
    np.testing.assert_array_equal(
        np.asarray(jnp.asarray(taps, jnp.bfloat16).astype(jnp.float32)), taps)
    assert 0.07 < taps.std() < 0.09
    for leaf in ("w_in", "conv_b", "D", "ssm_norm", "w_out"):
        assert held[leaf] is w[leaf]


# ---- operations and bytes ------------------------------------------------------------------

def test_ssd_counts_by_hand():
    dims = (64, 64, 8, 128)                    # heads, head_dim, groups, state
    assert ssd.conv_width(*dims) == 6144
    # a row's step: 2 MB of float32 state read and written, 3 x 6144 bf16 of
    # window read and written, xBC + dt + y of the token
    assert ssd.step_bytes(*dims) == 2 * 64 * 64 * 128 * 4 \
        + 2 * 3 * 6144 * 2 + (6144 + 64 + 4096) * 2
    # 32 rows x 23 layers at 819 GB/s: 3.8 ms a token step
    assert 0.0037 < 32 * 23 * ssd.step_bytes(*dims) / 819e9 < 0.0039
    assert ssd.step_flops(64, 64, 128) == 5 * 64 * 64 * 128
    # a chunk of 128: C B^T a group, the masked product, the chunk's state,
    # the incoming state's read-out
    assert ssd.chunk_flops(*dims) == 2 * 8 * 128 * 128 * 128 \
        + 2 * 64 * 128 * 128 * 64 + 2 * 2 * 64 * 128 * 64 * 128
    assert ssd.chunk_bytes(*dims) == 128 * (6144 + 64 + 4096) * 2 \
        + 2 * 64 * 64 * 128 * 4
    # the bytes bound: 8.3 us against 2.2 us of FLOPs
    assert ssd.chunk_bytes(*dims) / 819e9 > 3 * ssd.chunk_flops(*dims) / 197e12


# ---- the counter reader ----------------------------------------------------------------------

def _run(stats0, stats1, steps_log=None):
    c = loader.load(CELL)
    return types.SimpleNamespace(
        cell=c, trace=None,
        device={"peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}},
        window={"stats0": stats0, "stats1": stats1, "steps_log": steps_log})


def test_local_pick_share_on_made_up_counters():
    s0 = {"moe_rows_routed": 100, "moe_picks": 1000}
    s1 = {"moe_rows_routed": 100 + 552, "moe_picks": 1000 + 23 * 32 * 6}
    assert _reader("moe_local_pick_share").read(_run(s0, s1)) == \
        pytest.approx(12.5)


@pytest.mark.parametrize("metric", ["moe_local_pick_share", "ssm_share",
                                    "ssm_step_roofline", "ssm_scan_roofline",
                                    "paged_decode_block_roofline"])
def test_readers_find_nothing_on_a_program_without_the_names(metric):
    """The parent's engine has not got the counters, and a run without a
    trace has no scopes: nothing to read, nothing raised."""
    old = {"steps": 5, "moe_rows_routed": 9}
    assert _reader(metric).read(_run(old, dict(old, steps=9))) is None
    zero = {"moe_rows_routed": 0, "moe_picks": 0}
    assert _reader(metric).read(_run(zero, dict(zero))) is None


# ---- the trace readers, on a trace written by hand ------------------------------------------------

def _text(h, named=True):
    """One decode block of two token steps, 0-21 ms: a step (10.5 ms) the
    state's step 2 ms, then WITHOUT A NAME the write-back of the new state
    pool, started (0.1 ms) and waited for (0.2 ms) after the window's update
    0.5 ms, then the projections 1.5 ms (all three under pt.ssm), the
    experts 4 ms, attention 0.4 ms and its kernel 0.1 ms, the sampler
    1.5 ms, and without a name a weight's fetch 0.2 ms. Then one packed
    chunk 30-40 ms: the scan 3 ms under pt.ssm, 7 ms of matmuls. Host: a
    prefill span of 5 rows that begins inside the trace."""
    MS = h.MS
    blk = "jit(pt_decode_block)/while/body/"
    chk = "jit(pt_prefill_chunk)/"
    sc = (lambda s: s) if named else (lambda s: "")
    metas = {
        1: ("%while.1 = (s32[]) while(...)", "jit(pt_decode_block)/while"),
        2: ("%fusion.2 = f32[32,64,64,128]",
            blk + sc("pt.ssm/pt.ssm.step/") + "mul:"),
        3: ("%fusion.3 = bf16[32,3,6144]",
            blk + sc("pt.ssm/pt.ssm.conv/") + "select_n:"),
        4: ("%fusion.4 = bf16[32,10304]",
            blk + sc("pt.ssm/pt.ssm.in_proj/") + "dot_general:"),
        5: ("%fusion.5 = bf16[32,16,1856]",
            blk + sc("pt.moe/pt.moe.experts/") + "dot_general:"),
        6: ("%fusion.6 = bf16[32,4096]", blk + sc("pt.attn/") + "dot_general:"),
        7: ("%sort.7 = f32[32,16384]", blk + sc("pt.sampler/") + "sort:"),
        8: ("%fusion.8 = f32[32,8,8,128,128]",
            chk + sc("pt.ssm/pt.ssm.scan/") + "exp:"),
        9: ("%fusion.9 = bf16[4096,2688]", chk + sc("pt.moe/") + "dot_general:"),
        # the compiler's write-back of a layer's new state, and a weight's
        # fetch: no names
        14: ("%copy-start.14 = (f32[32,64,64,128]{3,2,1,0}, "
             "f32[32,64,64,128]{3,2,1,0:S(1)}, u32[]) "
             "copy-start(%get-tuple-element.5)", ""),
        12: ("%copy-done.12 = f32[32,64,64,128]{3,2,1,0} "
             "copy-done(%copy-start.14)", "jit(pt_decode_block)/while/body"),
        13: ("%copy-done.13 = bf16[2688]{0} copy-done(%copy-start.9)", ""),
        15: ('%pt_paged_decode.15 = bf16[32,2,16,128]{3,2,1,0} custom-call('
             '%p.1), custom_call_target="tpu_custom_call"',
             blk + sc("pt.attn/") + "pt_paged_decode"),
        10: (("jit_pt_decode_block" if named else "jit_run") + "(7)", ""),
        11: (("jit_pt_prefill_chunk" if named else "jit_chunk") + "(8)", ""),
    }
    ops = [h._event(1, 0, 21 * MS)]
    for s in (0, 10.5):
        t = s * MS
        for meta, dur in ((2, 2.0), (14, 0.1), (3, 0.5), (12, 0.2), (4, 1.5),
                          (5, 4.0), (6, 0.4), (15, 0.1), (7, 1.5), (13, 0.2)):
            ops.append(h._event(meta, int(t), int(dur * MS)))
            t += dur * MS
    ops += [h._event(8, 30 * MS, 3 * MS), h._event(9, 33 * MS, 7 * MS)]
    dev = h._plane(1, "/device:TPU:0", {
        "XLA Modules": [h._event(10, 0, 21 * MS), h._event(11, 30 * MS,
                                                           10 * MS)],
        "XLA Ops": ops}, metas, {1: "tf_op"})
    rows = 'stats { metadata_id: 2 int64_value: 5 }'
    host = h._plane(2, "/host:CPU", {"python3": [
        h._event(1, 0, 21 * MS), h._event(2, 25 * MS, 2 * MS, rows)]},
        {1: ("bench.engine.step", ""),
         2: (("pt." if named else "engine.") + "serve.prefill", "")},
        {2: "rows"})
    return dev + "\n" + host


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    h = _helpers()
    monkeypatch.setattr(_program, "ROOT", str(tmp_path))
    _program._CACHE.clear()
    out = {}
    for name, named in (("named", True), ("unnamed", False)):
        path = h._write(str(tmp_path), name, _text(h, named))
        # two token steps: 30 and 28 rows decoded
        log = [(2, [(100, 2)] * 28 + [(50, 1)] * 2, False)]
        run = _run({}, {}, steps_log=log)
        run.cell = types.SimpleNamespace(name=name, config=run.cell.config,
                                         spec=run.cell.spec)
        run.trace = trace_lib.reduce(path)
        out[name] = run
    return out


def test_scope_readers_known_answers(traced):
    run = traced["named"]
    # under pt.ssm 4 ms a step, and the state pool's unnamed write-back,
    # its start and its wait 0.3 ms (the weight's fetch is not the layers')
    assert _reader("ssm_share").read(run) == pytest.approx(100 * 4.3 / 10.5)
    # 58 row steps x 23 layers of step bytes over, a step, the 2.8 ms from
    # the step's start to the end of the write-back's wait: the step 2 ms,
    # the transfer from its start-op (which the wait names) to the wait's
    # end 0.8 ms, the window's update inside that
    need = 58 * 23 * ssd.step_bytes(64, 64, 8, 128) / 819e9
    assert _reader("ssm_step_roofline").read(run) == pytest.approx(
        100 * need / 5.6e-3)
    # the kernel's 0.1 ms a step against 6 attention layers' pages: 28
    # rows of 101 and 2 of 51 tokens at the first step, 28 of 102 at the
    # second (whole pages of 16), 2 KV heads of 128 under 32 query heads
    pages = 28 * 7 + 2 * 4 + 28 * 7
    kv = 6 * (2 * pages * 16 * 2 * 128 * 2 + 2 * (30 + 28) * 32 * 128 * 2)
    assert _reader("paged_decode_block_roofline").read(run) == pytest.approx(
        100 * kv / 819e9 / 0.2e-3)
    # 5 rows of one chunk each x 23 layers, bytes-bound, over 3 ms
    least = 5 * 23 * ssd.chunk_bytes(64, 64, 8, 128) / 819e9
    assert _reader("ssm_scan_roofline").read(run) == pytest.approx(
        100 * least / 3e-3)
    for metric in ("ssm_share", "ssm_step_roofline", "ssm_scan_roofline"):
        assert _reader(metric).read(traced["unnamed"]) is None


def test_a_pool_copy_is_told_by_kind_and_exact_dimensions(traced):
    """Not by a substring of the op's text: an op of another kind with the
    pool's shape, a copy of a longer shape that ends like it, and a named
    copy are none."""
    from chipbench.metrics import _ssm

    dims = _ssm.pool_dims(traced["named"])
    assert dims == {"32,64,64,128", "32,3,6144", "32,1,6144"}
    op = lambda name, stack="": _program.Op(name, stack, 0.0, 1.0)
    yes = ["%copy-done.6 = f32[32,64,64,128]{3,2,1,0:T(8,128)} copy-done("
           "%copy-start.6)", "%slice-done.2 = bf16[32,1,6144]{2,1,0} "
           "slice-done(%slice-start.2)", "%copy.3 = bf16[32,3,6144]{2,0,1} "
           "copy(%p)"]
    no = ["%fusion.2 = f32[32,64,64,128]{3,2,1,0} fusion(%p)",
          "%copy.9 = f32[2,32,64,64,128]{4,3,2,1,0} copy(%p)",
          "%copy-done.13 = bf16[2688]{0} copy-done(%copy-start.9)"]
    assert all(_ssm.is_pool_copy(op(n), dims) for n in yes)
    assert not any(_ssm.is_pool_copy(op(n), dims) for n in no)
    assert not _ssm.is_pool_copy(op(yes[0], "jit(f)/pt.ssm/copy"), dims)
    # a wait whose start the trace lacks stands for itself
    assert _ssm.transfers([op(yes[0])]) == [(0.0, 1.0)]


# ---- the cell's rehearsal ----------------------------------------------------------------------------

def test_rehearsal_is_correct_and_never_a_result():
    p = subprocess.run(
        [sys.executable, os.path.join(loader.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 3636), "--seconds", "8", "--trace",
         "1", "--rehearse"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900,
        stdin=subprocess.DEVNULL)
    assert p.returncode == 3, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # the counter's reader reads on the CPU too (4 of 8 experts held: well
    # over an eighth); the trace's three need a chip's name stacks
    assert 20 < line["metrics"]["moe_local_pick_share"]["value"] < 80
    for name in ("batch_occupancy", "kv_pool_used_share", "programs_built"):
        assert name in line["metrics"], name
    assert "prefix_hit_share" not in line["metrics"]
