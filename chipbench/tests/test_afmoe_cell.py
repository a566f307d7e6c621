"""What PR 40 added for ``trinity-mini.mixed-len-batch-16``: the
configuration's published widths and its two cuts, the family's weights out
of its leaf table, the window's pages against hand counts, the reference's
window and rotary by kind on a hand-made layer, each new reader on a trace
written by hand or on made-up counters (``None`` where there is nothing to
read, as on the parent's program), the traffic's lengths, and the cell's
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
from chipbench.ops import paged_decode, paged_decode_window

CELL = "trinity-mini.mixed-len-batch-16"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("attn_window_share", "attn_full_share", "paged_decode_window_roofline",
       "kv_window_pool_used_share", "window_pages_released_share")


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
    entry, = [c for c in bench["configs"] if c["name"] == "trinity-mini-ep8"]
    with open(os.path.join(loader.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "global_attn_every_n_layers": 4,
        "max_position_embeddings": 131072}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert entry["reduced"] == cfg["reduced"] == ["num_experts", "vocab_size"]
    assert (cfg["num_experts"], cfg["vocab_size"]) == (16, 25024)
    assert cfg["published"] == {"num_experts": 128, "vocab_size": 200192}
    assert entry["source"] == cfg["source"]
    for key in ("attention_gate", "norms", "qk_norm", "rotary",
                "embedding_scale", "expert_bias", "router_dtype",
                "initializer_range", "eos_token_id"):
        assert key in cfg["assumed"], key
    # the cut's arithmetic: 4.27 B parameters, 8.53 GB of bf16
    table = loader.load(CELL).leaf_table
    n = sum(int(np.prod(shape)) for leaves in (table["top"],)
            + tuple(table["layers"]) for _, shape, _ in leaves)
    assert 4.26e9 < n < 4.275e9
    attn = sum(int(np.prod(s)) for nme, s, _ in table["layers"][5]
               if nme in ("wq", "wk", "wv", "wg", "wo"))
    assert attn == 2048 * (4096 + 512 + 512 + 4096) + 4096 * 2048


def test_the_cell_is_listed_where_its_readers_read():
    with open(os.path.join(loader.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "mixed-len-batch-16"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW) <= listed
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
            assert m["unit"] == "%"
    # what reads another model's counts, or nothing since PR 39, is not
    assert not listed & {"decode_block_step_ms", "moe_gmm_roofline",
                         "decode_step_ms", "paged_decode_roofline",
                         "paged_decode_block_roofline"}
    assert {"prefix_hit_share", "kv_pool_used_share", "attn_share",
            "moe_share", "moe_local_pick_share", "programs_built"} <= listed
    e = loader.load(CELL).spec["engine"]
    assert (e["max_batch"], e["max_len"], e["page_size"], e["block_size"]) \
        == (16, 8192, 16, 8)


def test_the_traffic_is_long_and_unequal():
    c = loader.load(CELL)
    sched = c.generator.generate(c.traffic, 2 ** 31 + 40, 25024)
    first = sorted(len(r.prompt) for r in sched.requests[:16])
    assert first[0] == 198 and first[-1] == 7168 and first[8] == 1674
    assert sum(n > 2048 for n in first) == 6 and sum(first) == 37060
    # every round of 16 offers the same lengths
    assert sorted(len(r.prompt) for r in sched.requests[16:32]) == first
    assert max(len(r.prompt) + r.max_new for r in sched.requests) <= 8192
    assert all(3 <= int(r.prompt.min()) and int(r.prompt.max()) < 25024
               for r in sched.requests[:64])
    assert sched.eos_token_id == c.config["eos_token_id"]


def test_every_seed_sends_the_sizes_in_one_order():
    """``chat_fixed_order``: the sizes, their pairing, the greedy flags and
    the tenants are ``chat``'s at the file's ``order_seed`` whatever the
    seed; the seed draws the ids, the tenants' prefixes and the requests'
    sampling seeds, and the same seed draws them again."""
    from chipbench.generators import chat

    c = loader.load(CELL)
    assert c.traffic["kind"] == "chat_fixed_order"
    a = c.generator.generate(c.traffic, 3, 25024)
    b = c.generator.generate(c.traffic, 2 ** 31 + 5, 25024)
    order = chat.generate(c.traffic, c.traffic["order_seed"], 25024)
    sizes = lambda s: [(len(r.prompt), r.max_new, r.greedy, r.tenant)
                       for r in s.requests]
    assert sizes(a) == sizes(b) == sizes(order)
    assert sizes(a) != sizes(chat.generate(c.traffic, 3, 25024))
    assert a.fingerprint() != b.fingerprint()
    assert a.fingerprint() == c.generator.generate(
        c.traffic, 3, 25024).fingerprint()
    assert [r.seed for r in a.requests[:8]] != [r.seed
                                                for r in b.requests[:8]]
    # requests of one tenant share their 256-token prefix, within a seed
    mine = [r for r in a.requests[:64] if r.tenant == 1
            and len(r.prompt) >= 272]
    assert len(mine) > 2 and all(
        (r.prompt[:256] == mine[0].prompt[:256]).all() for r in mine)
    other = next(r for r in b.requests if r.tenant == 1
                 and len(r.prompt) >= 272)
    assert (other.prompt[:256] != mine[0].prompt[:256]).any()


def test_weights_layer_by_layer_are_the_whole_models_bits():
    import jax.numpy as jnp

    cell = loader.load(CELL, rehearse=True)
    table = cell.leaf_table
    kinds = [tuple(n for n, _, _ in leaves) for leaves in table["layers"]]
    attn = ("attn_norm", "wq", "wk", "wv", "wg", "wo", "q_gain", "k_gain",
            "post_attn_norm", "pre_mlp_norm")
    assert kinds[0] == attn + ("w_gate", "w_up", "w_down", "post_mlp_norm")
    assert kinds[1] == attn + (
        "router", "expert_bias", "experts_gate", "experts_up",
        "experts_down", "shared_gate", "shared_up", "shared_down",
        "post_mlp_norm")
    by_kind = {n: k for leaves in table["layers"] for n, _, k in leaves}
    assert by_kind["expert_bias"] == "normal"
    assert {by_kind[n] for n in ("attn_norm", "post_attn_norm",
                                 "pre_mlp_norm", "post_mlp_norm", "q_gain",
                                 "k_gain")} == {"gain"}
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


# ---- the reference's window and rotary, by kind ------------------------------------------

def test_the_references_window_and_rotary_are_by_kind():
    """On a hand-made layer: a key ``window`` or more behind the query is
    not seen by a sliding layer and is by a full one; rotary turns a sliding
    layer's q and k (a shifted input gives another answer) and nothing of a
    full layer's; the blocks of queries change nothing."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import afmoe as ref

    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.3, s), jnp.float32)
    w = {"wq": f(32, 64), "wk": f(32, 32), "wv": f(32, 32), "wg": f(32, 64),
         "wo": f(64, 32), "q_gain": jnp.ones(16), "k_gain": jnp.ones(16)}
    a = f(40, 32)

    def op(a, window, **kw):
        return ref.attention_op(w, a, window=window, n_heads=4, n_kv=2,
                                rotary=window is not None, eps=1e-5,
                                theta=1e4, **kw)

    with jax.default_matmul_precision("highest"):
        moved = a.at[0].add(1.0)
        for window, seen_until in ((8, 8), (None, 40)):
            y = op(a, window)
            d = np.abs(np.asarray(op(moved, window) - y)).max(-1)
            assert (d[:seen_until] > 0).all() and (d[seen_until:] == 0).all()
            np.testing.assert_allclose(np.asarray(op(a, window, q_block=7)),
                                       np.asarray(y), atol=1e-5)
        # positions: the last 20 rows alone are positions 0..19 to the op;
        # a sliding layer's rotary is relative: row 39 sees rows 32..39
        # either way, at other absolute positions, and answers alike
        np.testing.assert_allclose(np.asarray(op(a[20:], 8))[-1],
                                   np.asarray(op(a, 8))[-1], atol=1e-4)
        # without rotary a full layer's answer over the same keys is alike
        # whatever the positions are called
        np.testing.assert_allclose(np.asarray(op(a[:20], None))[-1],
                                   np.asarray(op(a, None))[19], atol=1e-5)
        q = (a @ w["wq"]).reshape(40, 4, 16)
        assert np.abs(np.asarray(ref._rope(q, 1e4) - q))[1:].max() > 1e-2


def test_one_padded_length_changes_no_position(monkeypatch):
    """Sequences past 512 positions all run at one length, the blocks of
    padding skipped: every real position reads what it reads unpadded."""
    import jax.numpy as jnp

    from chipbench.reference import afmoe as ref

    cell = loader.load(CELL, rehearse=True)
    cfg, table = cell.config, cell.leaf_table
    seed = 2 ** 31 + 9
    top = W.top_weights(table, seed)
    layer = lambda i: W.layer_weights(table, seed, i)
    rng = np.random.default_rng(1)
    many = [rng.integers(3, 512, (1, n)).astype(np.int32)
            for n in (1100, 530, 40)]
    assert ref._padded([1100, 530, 40]) == [2048, 2048, 40]
    got = ref.hidden_states_many(cfg, many, layer, top)
    monkeypatch.setattr(ref, "ONE_LENGTH_FROM", 10 ** 6)
    want = ref.hidden_states_many(cfg, many, layer, top)
    for g, w_, ids in zip(got, want, many):
        assert g.shape == w_.shape == (1, ids.shape[1], 65)
        h = np.asarray(g)[0, :, :64] - np.asarray(w_)[0, :, :64]
        assert np.abs(h).max() < 2e-4
        np.testing.assert_allclose(np.asarray(g)[0, :, 64],
                                   np.asarray(w_)[0, :, 64], atol=1e-5)


# ---- operations and bytes ------------------------------------------------------------------

def test_window_pages_by_hand():
    wp = paged_decode_window.window_pages
    # within the window: every page of the context
    assert [wp(n, 2048, 16) for n in (0, 1, 16, 17, 2048)] == [
        0, 1, 1, 2, 128]
    # past it: from the page of n - 2048 to the page of n - 1
    assert wp(2049, 2048, 16) == 129            # pages 0..128
    assert wp(2064, 2048, 16) == 128            # pages 1..128
    assert wp(6000, 2048, 16) == 128            # pages 247..374 of 375
    assert wp(6001, 2048, 16) == 129            # pages 247..375
    shape = (4, 32, 128, 16)
    one = paged_decode_window.paged_decode_window_bytes([6001], 2048, *shape)
    assert one == 2 * 129 * 16 * 4 * 128 * 2 + 2 * 32 * 128 * 2
    # a context inside the window reads what a full layer reads
    assert paged_decode_window.paged_decode_window_bytes(
        [1500, 0, 300], 2048, *shape) == paged_decode.paged_decode_bytes(
        [1500, 0, 300], *shape)
    # the saving at 6,000: a third of a full layer's bytes
    assert 0.33 < one / paged_decode.paged_decode_bytes([6001], *shape) < 0.36


# ---- the counter readers ----------------------------------------------------------------------

def _run(stats0, stats1, steps_log=None):
    c = loader.load(CELL)
    return types.SimpleNamespace(
        cell=c, trace=None,
        device={"peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}},
        window={"stats0": stats0, "stats1": stats1, "steps_log": steps_log})


def test_counter_readers_on_made_up_counters():
    s0 = {"steps": 10, "window_pages_in_use_steps": 1000,
          "window_pages_released": 50, "window_pages_allocated": 100,
          "kv_pool_pages.full": 8256, "kv_pool_pages.sliding": 2000}
    s1 = dict(s0, steps=110, window_pages_in_use_steps=1000 + 100 * 1500,
              window_pages_released=50 + 600, window_pages_allocated=1100)
    assert _reader("kv_window_pool_used_share").read(_run(s0, s1)) == \
        pytest.approx(75.0)
    assert _reader("window_pages_released_share").read(_run(s0, s1)) == \
        pytest.approx(60.0)


@pytest.mark.parametrize("metric", NEW)
def test_readers_find_nothing_on_a_program_without_the_names(metric):
    """The parent's engine has not got the counters, an engine with one
    group maps no window page, and a run without a trace has no scopes:
    nothing to read, nothing raised."""
    old = {"steps": 5, "decode_blocks": 3}
    assert _reader(metric).read(_run(old, dict(old, steps=9))) is None
    zero = {"steps": 5, "window_pages_in_use_steps": 0,
            "window_pages_released": 0, "window_pages_allocated": 0,
            "kv_pool_pages.full": 100}
    assert _reader(metric).read(_run(zero, dict(zero, steps=9))) is None


# ---- the trace readers, on a trace written by hand ------------------------------------------------

def _text(h, named=True):
    """One decode block of two token steps, 0-20 ms: a step (10 ms) a window
    layer's projections 1 ms, its append 0.2 ms and its kernel 0.3 ms, its
    gate 0.5 ms; a full layer's projections 1 ms and its kernel 1 ms; the
    experts 4 ms, the head 1 ms, the sampler 1 ms."""
    MS = h.MS
    blk = "jit(pt_decode_block)/while/body/"
    sc = (lambda s: s) if named else (lambda s: "")
    win, full = sc("pt.attn/pt.attn.window/"), sc("pt.attn/pt.attn.full/")
    metas = {
        1: ("%while.1 = (s32[]) while(...)", "jit(pt_decode_block)/while"),
        2: ("%fusion.2 = bf16[16,4096]", blk + win + "dot_general:"),
        3: ("%fusion.3 = bf16[2597,4,16,128]",
            blk + win + sc("pt.kv_write/") + "scatter:"),
        4: ('%pt_paged_decode.4 = bf16[16,4,8,128]{3,2,1,0} custom-call('
            '%p.1), custom_call_target="tpu_custom_call"',
            blk + win + "pt_paged_decode"),
        5: ("%fusion.5 = bf16[16,4096]",
            blk + win + sc("pt.attn.gate/") + "logistic:"),
        6: ("%fusion.6 = bf16[16,4096]", blk + full + "dot_general:"),
        7: ('%pt_paged_decode.7 = bf16[16,4,8,128]{3,2,1,0} custom-call('
            '%p.2), custom_call_target="tpu_custom_call"',
            blk + full + "pt_paged_decode"),
        8: ("%fusion.8 = bf16[16,16,1024]",
            blk + sc("pt.moe/pt.moe.experts/") + "dot_general:"),
        9: ("%fusion.9 = f32[16,25024]", blk + sc("pt.lm_head/")
            + "dot_general:"),
        12: ("%fusion.12 = f32[16]", blk + sc("pt.sampler/") + "reduce:"),
        10: (("jit_pt_decode_block" if named else "jit_run") + "(7)", ""),
    }
    ops = [h._event(1, 0, 20 * MS)]
    for s in (0, 10):
        t = s * MS
        for meta, dur in ((2, 1.0), (3, 0.2), (4, 0.3), (5, 0.5), (6, 1.0),
                          (7, 1.0), (8, 4.0), (9, 1.0), (12, 1.0)):
            ops.append(h._event(meta, int(t), int(dur * MS)))
            t += dur * MS
    dev = h._plane(1, "/device:TPU:0", {
        "XLA Modules": [h._event(10, 0, 20 * MS)], "XLA Ops": ops},
        metas, {1: "tf_op"})
    host = h._plane(2, "/host:CPU", {"python3": [h._event(1, 0, 20 * MS)]},
                    {1: ("bench.engine.step", "")}, {})
    return dev + "\n" + host


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    h = _helpers()
    monkeypatch.setattr(_program, "ROOT", str(tmp_path))
    _program._CACHE.clear()
    out = {}
    for name, named in (("named", True), ("unnamed", False)):
        path = h._write(str(tmp_path), name, _text(h, named))
        # two token steps: rows of 300 and of 6,000, and one that ended
        # after the first step
        log = [(2, [(300, 2), (6000, 2), (2100, 1)], False)]
        run = _run({}, {}, steps_log=log)
        run.cell = types.SimpleNamespace(name=name, config=run.cell.config,
                                         spec=run.cell.spec)
        run.trace = trace_lib.reduce(path)
        out[name] = run
    return out


def test_scope_readers_known_answers(traced):
    run = traced["named"]
    assert _reader("attn_window_share").read(run) == pytest.approx(
        100 * 2.0 / 10.0)
    assert _reader("attn_full_share").read(run) == pytest.approx(
        100 * 2.0 / 10.0)
    assert _reader("attn_share").read(run) == pytest.approx(100 * 4.0 / 10.0)
    # the kernels' 1.3 ms a step against, a step, 24 window layers' pages
    # (a context's last 2,048 tokens' pages) and 8 full layers' (all):
    # contexts 300, 6,000 and 2,100 at the first step, 301 and 6,001 at
    # the second; whole pages of 16; 4 KV heads of 128 under 32 query heads
    wp = paged_decode_window.window_pages
    ceil = lambda n: -(-n // 16)
    steps = ([300, 6000, 2100], [301, 6001])
    window = sum(wp(n, 2048, 16) for ctx in steps for n in ctx)
    full = sum(ceil(n) for ctx in steps for n in ctx)
    assert window == 19 + 128 + 129 + 19 + 129 and full == 19 + 375 + 132 \
        + 19 + 376
    page_bytes, qo = 2 * 16 * 4 * 128 * 2, 2 * 32 * 128 * 2
    need = (24 * window + 8 * full) * page_bytes + 32 * 5 * qo
    assert _reader("paged_decode_window_roofline").read(run) == \
        pytest.approx(100 * need / 819e9 / 2.6e-3)
    for metric in NEW[:3]:
        assert _reader(metric).read(traced["unnamed"]) is None


# ---- the cell's rehearsal ----------------------------------------------------------------------------

def test_two_seeds_ask_the_engine_for_the_same_steps():
    """What ``chat_fixed_order`` is for, at the rehearsal size: the requests
    of two seeds differ in every id, and the engine packs the same rows and
    decodes the same steps for them, step for step, with outputs as long
    (which slot a request sits in apart). Under ``chat`` the two part ways
    in the first steps. No request carries an EOS id the 512-token
    vocabulary could draw: an early end is the one thing a seed may move."""
    from chipbench import control

    cell = loader.load(CELL, rehearse=True)
    cell.traffic = dict(cell.traffic, requests=48, eos_token_id=600)

    def steps(seed, bench=None):
        # a run's engine is new: what an earlier window left in the trie
        # moves an admission by a step
        bench = bench or control.ServingBench(cell, 1)
        eng = bench.engine
        log, real, s0 = [], eng.step, dict(eng.stats)

        def step():
            real()
            log.append((sorted(len(r.output)
                               for r in eng._occupied.values()),
                        eng.stats["packed_rows"] - s0["packed_rows"],
                        eng.stats["decode_block_steps"]
                        - s0["decode_block_steps"]))

        eng.step = step
        try:
            win, _ = bench.window(seed, 1e9)
        finally:
            del eng.step
        assert len(win["done"]) == 48 and win["exhausted"]
        return log, bench

    a, _ = steps(11)
    b, bench = steps(2 ** 31 + 77)
    assert a == b
    cell.traffic = dict(cell.traffic, kind="chat")
    cell.generator = loader._module("generators", "chat", "chat")
    a, b = steps(11, bench)[0], steps(2 ** 31 + 77, bench)[0]
    assert [x[1:] for x in a[:8]] != [x[1:] for x in b[:8]]


def test_rehearsal_is_correct_and_never_a_result():
    p = subprocess.run(
        [sys.executable, os.path.join(loader.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 4040), "--seconds", "8", "--trace",
         "1", "--rehearse"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900,
        stdin=subprocess.DEVNULL)
    assert p.returncode == 3, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # the counters' readers read on the CPU too; the trace's three need a
    # chip's name stacks
    m = line["metrics"]
    assert 20 < m["moe_local_pick_share"]["value"] < 80
    assert 10 < m["kv_window_pool_used_share"]["value"] <= 100
    assert 10 < m["window_pages_released_share"]["value"] < 100
    assert m["prefix_hit_share"]["value"] > 5
    for name in ("batch_occupancy", "kv_pool_used_share", "programs_built"):
        assert name in m, name
