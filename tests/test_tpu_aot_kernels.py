"""Kernels of the serving path compiled for a described TPU v5e, at real
widths, with no chip attached: Mosaic refuses here what it would refuse on
the chip (a slice off the tiling, too much VMEM, an op it cannot lower),
which interpret mode never sees. Nothing runs, so nothing here says a
result is right or fast.

The topology is described inside a fixture, never at import: only the
worker that runs this file may load the TPU's library.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.paged_attention import (PageState, append_paged_chunk,
                                            kv_pool_shape, page_state_write,
                                            paged_decode_attention,
                                            paged_prefill_attention,
                                            pool_pages)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (id, rows, q heads, kv heads, head_dim, page, pages a row, pool pages, dtype)
_PAGED_DECODE_SHAPES = [
    # the chat-batch cell: internlm2-1.8b, 24 slots of 2048, page 16
    ("cell-gqa16x8", 24, 16, 8, 128, 16, 128, 3329, jnp.bfloat16),
    # its tp=4 and tp=8 shards: the local heads only
    ("tp-shard-2kv", 24, 4, 2, 128, 16, 128, 3329, jnp.bfloat16),
    ("tp-shard-1kv", 24, 2, 1, 128, 16, 128, 3329, jnp.bfloat16),
    # chip_smoke's server: MHA 16/16, 8 pages a row
    ("smoke-mha16", 8, 16, 16, 128, 16, 8, 64, jnp.bfloat16),
    # a table shorter than any chunk, and float32 pools
    ("two-pages", 8, 16, 16, 128, 16, 2, 17, jnp.bfloat16),
    ("f32-pools", 4, 8, 2, 128, 8, 150, 600, jnp.float32),
    # the chat-batch-64 cell: lfm2-24b-a2b, 64 slots of 2560, page 16; heads
    # of 64 stored two to a 128-lane row, read by the same kernel
    ("cell-lfm2-gqa32x8-d64", 64, 32, 8, 64, 16, 160, 10497, jnp.bfloat16),
    # the chat-short-batch-32 cell: nemotron-3-nano, 32 slots of 1536, two
    # KV heads of 128 under 16 query heads each
    ("cell-nemotron-gqa32x2", 32, 32, 2, 128, 16, 96, 3152, jnp.bfloat16),
    # four heads of 32 to a row, and float32 pools of heads of 64
    ("mha8-d32", 8, 8, 8, 32, 16, 72, 600, jnp.bfloat16),
    ("f32-d64", 4, 8, 4, 64, 8, 150, 600, jnp.float32),
]


@pytest.mark.parametrize("shape", _PAGED_DECODE_SHAPES, ids=lambda s: s[0])
def test_paged_decode_compiles_for_v5e(shape, one_chip, monkeypatch):
    _, b, hq, hkv, d, page, maxp, n_pages, dtype = shape
    # the dispatch guard asks for the backend; steer it here, not by an option
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    # the pool as an engine stores it: lane-dense where heads are narrow
    pool = kv_pool_shape(n_pages, hkv, page, d, dtype)
    assert pool[-1] == 128
    lowered = jax.jit(paged_decode_attention).trace(
        sds((b, hq, d), dtype), sds(pool, dtype), sds(pool, dtype),
        sds((b, maxp), jnp.int32),
        sds((b,), jnp.int32)).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1, "one Pallas call, no fallback"
    assert "pt_paged_decode" in text
    lowered.compile()


@pytest.mark.parametrize("shape", [
    # heads that do not fill the lanes and do not fold: the dense gather
    ("gqa8x4-d96", 8, 8, 4, 96, 16, 8, 64, jnp.bfloat16),
    ("one-kv-d64", 8, 4, 1, 64, 16, 8, 64, jnp.bfloat16),
    # a hand-built logical pool of heads of 64 keeps the parent's path
    ("logical-d64", 8, 32, 8, 64, 16, 8, 64, jnp.bfloat16),
], ids=lambda s: s[0])
def test_what_does_not_fold_lowers_to_the_gather(shape, one_chip,
                                                 monkeypatch):
    name, b, hq, hkv, d, page, maxp, n_pages, dtype = shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    pool = (n_pages, hkv, page, d)
    if name != "logical-d64":
        assert kv_pool_shape(n_pages, hkv, page, d, dtype) == pool
    text = jax.jit(paged_decode_attention).trace(
        sds((b, hq, d), dtype), sds(pool, dtype), sds(pool, dtype),
        sds((b, maxp), jnp.int32),
        sds((b,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text


# (id, rows, queries a row, q heads, kv heads, head_dim, pages a row, window,
#  dtype): the packed chunk's attention in the four serving cells
_PAGED_CHUNK_SHAPES = [
    ("trinity-full-gqa32x4", 16, 128, 32, 4, 128, 512, None, jnp.bfloat16),
    ("trinity-window-2048", 16, 128, 32, 4, 128, 512, 2048, jnp.bfloat16),
    ("internlm2-gqa16x8", 16, 128, 16, 8, 128, 128, None, jnp.bfloat16),
    ("lfm2-gqa32x8-d64", 64, 128, 32, 8, 64, 160, None, jnp.bfloat16),
    ("nemotron-gqa32x2", 32, 128, 32, 2, 128, 96, None, jnp.bfloat16),
    # the longest chunk the kernel takes: a head's rows in two tiles
    ("chunk-of-512", 4, 512, 32, 4, 128, 128, None, jnp.bfloat16),
    # one row, a chunk of one page of queries, float32 pools
    ("f32-one-row", 1, 16, 8, 2, 128, 40, 100, jnp.float32),
]


@pytest.mark.parametrize("shape", _PAGED_CHUNK_SHAPES, ids=lambda s: s[0])
def test_paged_chunk_compiles_for_v5e(shape, one_chip, monkeypatch):
    _, b, s, hq, hkv, d, maxp, window, dtype = shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    pool = kv_pool_shape(b * maxp // 4 + 17, hkv, 16, d, dtype)
    assert pool[-1] == 128
    fn = functools.partial(paged_prefill_attention, window=window)
    lowered = jax.jit(fn).trace(
        sds((b, s, hq, d), dtype), sds(pool, dtype), sds(pool, dtype),
        sds((b, maxp), jnp.int32),
        sds((b,), jnp.int32)).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1, "one Pallas call, no fallback"
    assert "pt_paged_chunk" in text
    # no float32 score tensor over the table's extent is left beside it
    assert lowered.compile().memory_analysis().temp_size_in_bytes < (
        b * s * hq * maxp * 16 * 4) // 8


def test_a_verify_window_lowers_to_the_reference(one_chip, monkeypatch):
    """``K + 1`` positions are no whole tile of queries: the one dispatch
    sends them to the dense gather on a TPU too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    pool = (64, 8, 16, 128)
    text = jax.jit(paged_prefill_attention).trace(
        sds((8, 5, 16, 128), jnp.bfloat16), sds(pool, jnp.bfloat16),
        sds(pool, jnp.bfloat16), sds((8, 8), jnp.int32),
        sds((8,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text


def _lowered_sampling_block(eng, one_chip, n_steps=4):
    """An engine's sampling decode block, traced and lowered for the
    described v5e."""
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    tree = lambda t: jax.tree_util.tree_map(sds, t)
    vec = lambda dt: jax.ShapeDtypeStruct((eng.max_batch,), dt,
                                          sharding=one_chip)
    return eng._build_mega_jit().trace(
        tree(eng._params), vec(jnp.int32), tree(eng.caches["kv"]),
        sds(eng.caches["tables"]), vec(jnp.int32), vec(jnp.bool_),
        vec(jnp.int32), vec(jnp.float32), vec(jnp.float32), vec(jnp.int32),
        n_steps=n_steps, do_sample=True).lower(lowering_platforms=("tpu",))


def _one_lg_and_one_e(hlo, rows, V):
    """The sampler's passes all read ONE ``lg`` and ONE ``e``: left to
    itself the compiler recomputes the division by the temperature and the
    ``exp`` inside every fusion of a block that reads them (five and three
    in the LFM2 block), and on the chip the copies are not equal (a probe
    read them 1,000 float32 places or more apart), which at temperature 1e-6
    dropped the best token (PR 39, before its ``optimization_barrier``s:
    ``served_token_logit_gap`` 5.9-6.4). And no
    ``sort`` over the vocabulary is left (a router's is not one)."""
    import re

    wide = [body for body in re.findall(
        r"(?ms)^%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n(.*?)^\}", hlo)
        if f"f32[{rows},{V}]" in body]
    assert wide, "the sampler's fusions appear in the compiled block"
    assert sum(" divide(" in body for body in wide) == 1
    assert sum(" exponential(" in body for body in wide) == 1
    assert not [line for line in hlo.splitlines()
                if " sort(" in line and f"[{rows},{V}]" in line]


def test_lfm2_decode_block_holds_the_kernel_and_no_pool_copy(one_chip,
                                                             monkeypatch):
    """The decode block of an LFM2 engine at the chat-batch-64 cell's head
    shapes (32/8 heads of 64, page 16, bf16; two attention layers of five,
    small experts and vocabulary), lowered and compiled for the described v5e: one
    ``pt_paged_decode`` call an attention layer, the pools appended in place
    in the default layout, and no pool-shaped ``copy`` (the layout
    conversions PR 30 found round a scatter that XLA lays out slot-major).

    And the sampler's passes all read one ``lg`` and one ``e``
    (``_one_lg_and_one_e``)."""
    import re

    from _lfm2_util import TINY, engine
    from chipbench.adapters import lfm2_block

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dict(TINY, hidden_size=2048, num_attention_heads=32,
               num_key_value_heads=8, layer_types=[
                   "conv", "full_attention", "conv", "full_attention",
                   "conv"])
    model = lfm2_block.build_model(cfg, max_positions=512, dtype="bfloat16")
    eng = engine(model, max_batch=8, max_len=512, page_size=16, block_size=4)
    pools = [e for e in eng.caches["kv"] if isinstance(e, tuple)]
    assert len(pools) == 2 and eng.stats["paged_kernel_layers"] == 2
    shape = tuple(pools[0][0].shape)
    assert shape[1:] == (4, 16, 128)
    lowered = _lowered_sampling_block(eng, one_chip)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 2
    assert text.count("pt_paged_decode") >= 2
    hlo = lowered.compile().as_text()
    dims = ",".join(map(str, shape))
    pool_ops = re.findall(
        rf"= bf16\[{dims}\]\{{([0-9,]+)[^}}]*\}} (\S+?)\(", hlo)
    assert pool_ops, "the pools appear in the compiled program"
    # every pool-shaped value keeps the default layout (a conversion would
    # show as {3,1,2,0}), and none is a copy; an asynchronous copy-start /
    # copy-done may move a pool this small between memory spaces
    assert {layout for layout, _ in pool_ops} == {"3,2,1,0"}, pool_ops
    assert "copy" not in {op for _, op in pool_ops}, pool_ops
    _one_lg_and_one_e(hlo, eng.max_batch, cfg["vocab_size"])


def _llama_engine():
    """The chat-batch cell's family at a small width: heads 4/2 of 128, two
    layers, bf16 (the lm head's logits are bf16 widened, as in the cell)."""
    from _lfm2_util import engine
    from chipbench.adapters import llama_block

    cfg = dict(vocab_size=4096, hidden_size=512, intermediate_size=1024,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, rms_norm_eps=1e-5, rope_theta=1e6)
    model = llama_block.build_model(cfg, max_positions=512, dtype="bfloat16")
    return engine(model, max_batch=8, max_len=512, page_size=16,
                  block_size=4), cfg["vocab_size"]


def _nemotron_engine():
    """The chat-short-batch-32 cell's family, all three mixers, tiny."""
    from _nemotron_h_util import TINY, engine
    from chipbench.adapters import nemotron_h_block

    model = nemotron_h_block.build_model(TINY, max_positions=128,
                                         dtype="bfloat16")
    return engine(model, max_batch=8, max_len=64, page_size=4,
                  block_size=4), TINY["vocab_size"]


@pytest.mark.parametrize("family", [_llama_engine, _nemotron_engine],
                         ids=["llama", "nemotron_h"])
def test_a_sampling_decode_block_computes_lg_and_e_once(family, one_chip):
    """The other two families' sampling blocks, compiled for the described
    v5e (plain paths: what is held here is the sampler's fusions beside a
    model's, not a kernel): one division and one ``exp`` over ``[rows, V]``,
    and no sort of the vocabulary."""
    eng, V = family()
    hlo = _lowered_sampling_block(eng, one_chip).compile().as_text()
    _one_lg_and_one_e(hlo, eng.max_batch, V)


# (id, chunk rows, chunk tokens, kv heads, head_dim, pages a row, pages asked)
_CHUNK_APPEND_SHAPES = [
    # the chat-batch cell's packed chunk: 24 slots of 2048 + 256 + parking
    ("cell-chat-batch", 8, 128, 8, 128, 128, 3329),
    # chat-batch-64's, lane-dense, at 8 rows and at warm-up's widest wave
    ("cell-chat-batch-64", 8, 128, 8, 64, 160, 10497),
    ("cell-chat-batch-64-wave", 64, 128, 8, 64, 160, 10497),
]


@pytest.mark.parametrize("shape", _CHUNK_APPEND_SHAPES, ids=lambda s: s[0])
def test_chunk_append_compiles_in_place_by_the_page(shape, one_chip):
    """The packed chunk's append for the described v5e: one scatter a pool
    on ``rows * tokens // page`` page indices (the row form spends ``rows *
    tokens * head groups``), the donated pools aliased, nothing copied."""
    _, b, s, hkv, d, maxp, asked = shape
    bf, page = jnp.bfloat16, 16
    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    pool = kv_pool_shape(pool_pages(asked, bf), hkv, page, d, bf)
    assert pool[0] % 16 == 0 and pool[-1] == 128
    lowered = jax.jit(
        functools.partial(append_paged_chunk, page_aligned=True),
        donate_argnums=(0, 1)).trace(
        sds(pool, bf), sds(pool, bf), sds((b, s, hkv, d), bf),
        sds((b, s, hkv, d), bf), sds((b, maxp), jnp.int32),
        sds((b,), jnp.int32)).lower(lowering_platforms=("tpu",))
    n = b * s // page
    text = lowered.as_text()
    assert text.count('"stablehlo.scatter"(') == 2
    assert text.count(f"xbf16>, tensor<{n}x1xi32>, tensor<{n}x") == 2
    ma = lowered.compile().memory_analysis()
    assert ma.alias_size_in_bytes == 2 * 2 * int(np.prod(pool))
    assert ma.temp_size_in_bytes == 0


def test_ring_write_at_the_engines_page_count_copies_nothing(one_chip):
    """``page_state_write`` on a conv ring of chat-batch-64 (hidden 2048,
    three slots, 16 x 128 tokens): the device keeps the ring slot-major and
    the scatter collapses ``[slots, pages]`` to rows, a bitcast where the
    tile's rows divide the pages. At the 10,497 pages the engine asks for
    the compiled program copied the ring in and out (temporaries
    129,265,664 B); at ``pool_pages`` of them it copies nothing."""
    bf, rows = jnp.bfloat16, 16 * 128
    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    pages = pool_pages(10497, bf)
    assert pages == 10512

    def write(ring, vals, tables, pos, seq, valid):
        return page_state_write(PageState(ring, 16), vals, tables, pos, seq,
                                valid).ring

    ma = jax.jit(write, donate_argnums=(0,)).trace(
        sds((pages, 3, 2048), bf), sds((rows, 2048), bf),
        sds((16, 160), jnp.int32), sds((rows,), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.bool_)).lower(
        lowering_platforms=("tpu",)).compile().memory_analysis()
    assert ma.alias_size_in_bytes == pages * 3 * 2048 * 2
    assert ma.temp_size_in_bytes == 0


# ---- Mamba-2's scan and step, the relu^2 experts (chat-short-batch-32) -------

_SSD = dict(heads=64, p=64, groups=8, n=128)     # nemotron-3-nano's widths


def _ssd_args(sds, lead):
    bf, f32 = jnp.bfloat16, jnp.float32
    d = _SSD
    return (sds((*lead, d["heads"], d["p"]), bf),           # x
            sds((*lead, d["heads"]), f32),                   # dt
            sds((d["heads"],), f32),                         # A
            sds((*lead, d["groups"], d["n"]), bf),           # B
            sds((*lead, d["groups"], d["n"]), bf),           # C
            sds((d["heads"],), f32))                         # D


def test_ssd_packed_chunk_compiles_for_v5e_with_the_pool_in_place(one_chip):
    """``ssd_scan_pooled`` on the cell's widest pack (32 rows of one chunk
    of 128) over the pool of 32 slots: the pool is donated and comes back
    aliased; the temporaries (the ``[chunk, chunk]`` decays of 64 heads a
    row in float32, the rows' states) stay under 1.2 GB."""
    from paddle_tpu.ops import ssd

    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    pool = (32, 64, 64, 128)
    ma = jax.jit(functools.partial(ssd.ssd_scan_pooled, chunk=128),
                 donate_argnums=(0,)).trace(
        sds(pool, jnp.float32), *_ssd_args(sds, (32, 128)),
        sds((32,), jnp.int32), sds((32,), jnp.bool_),
        sds((32,), jnp.int32)).lower(
        lowering_platforms=("tpu",)).compile().memory_analysis()
    assert ma.alias_size_in_bytes == int(np.prod(pool)) * 4
    assert ma.temp_size_in_bytes < 1.2e9


def _while_bodies(hlo):
    """{name: lines} of the computations some ``while`` of the compiled text
    names as its body."""
    import re

    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    assert bodies and bodies <= set(comps)
    return {b: comps[b] for b in bodies}


@pytest.mark.parametrize("rows", [8, 32])
def test_ssd_packed_chunk_in_a_deep_program_copies_no_pool_in_a_loop(
        rows, one_chip):
    """What the parent's op did only INSIDE the cell's chunk program: the
    v5e compiler moved the layer's whole pool, the carry of the scan over
    the pack's rows, into its second memory space and back in every
    iteration (``copy f32[32,64,64,128]{..S(1)}`` and a ``copy`` of that, a
    Mamba layer, in the ``while`` body: 96 us each on the chip, PERF.md
    section 6, PR 37). Alone the op never copied, nor did two mixers with
    a matmul between, nor twelve (compiled here for the described v5e, PR
    37): the cell's 23 Mamba-2 mixers' ``paged_chunk`` at its widths, each
    on a pool of its own with a matmul between, reproduce the parent's 46
    copies at 8 and at 32 rows; one mixer's weights serve all 23, so that
    nothing of 1.8 GB is drawn here. With one sequence's state as the carry
    no ``while`` body holds a ``copy`` or ``copy-start`` of the pool's
    dimensions (at 32 rows the stacked states have them too), and the
    pools still come back in place."""
    import re

    from paddle_tpu.jit.api import _Swap, _collect_state
    from paddle_tpu.models.nemotron_h.modeling import (NemotronHConfig,
                                                       NemotronHMamba2)
    from paddle_tpu.ops.paged_attention import SeqState

    layers, slots = 23, 32
    cfg = NemotronHConfig(hybrid_override_pattern="M")
    mixer = NemotronHMamba2(cfg)
    _, tensors = _collect_state(mixer)
    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    pool = (slots, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.ssm_state_size)
    state = SeqState(sds(pool, jnp.float32),
                     sds((slots, cfg.conv_kernel - 1, cfg.conv_width),
                         jnp.bfloat16))

    def program(params, u, w, states, seq_slots, starts, count):
        with _Swap(tensors, params):
            out = []
            for st in states:
                y, st = mixer.paged_chunk(u, st, seq_slots, starts, count)
                u = u + jnp.matmul(y, w)
                out.append(st)
        return u, out

    vec = sds((rows,), jnp.int32)
    compiled = jax.jit(program, donate_argnums=(3,)).trace(
        [sds(t._data.shape, t._data.dtype) for t in tensors],
        sds((rows, cfg.chunk_size, cfg.hidden_size), jnp.bfloat16),
        sds((cfg.hidden_size, cfg.hidden_size), jnp.bfloat16),
        [state] * layers, vec, vec, vec).lower(
        lowering_platforms=("tpu",)).compile()
    dims = ",".join(map(str, pool))
    moved = re.compile(rf"= \(?f32\[{dims}\]\S* .*?\b(copy|copy-start)\(")
    copies = [line.strip()[:120]
              for body in _while_bodies(compiled.as_text()).values()
              for line in body if moved.search(line.split(", metadata")[0])]
    assert not copies, copies
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= layers * int(np.prod(pool)) * 4
    assert ma.temp_size_in_bytes < 0.7e9


@pytest.mark.parametrize("by", ["live", "slots"])
def test_ssd_step_compiles_for_v5e_and_updates_the_pool_in_place(by,
                                                                 one_chip):
    """The one-token step over 32 slots: donated, the pool comes back
    aliased (no second copy of a layer's 64 MB); by the decode block's mask
    the pass needs no temporary of the pool's size at all."""
    from paddle_tpu.ops import ssd

    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    pool = (32, 64, 64, 128)
    key = sds((32,), jnp.bool_) if by == "live" else sds((32,), jnp.int32)

    def step(pool, x, dt, a, b, c, d, fresh, key):
        return ssd.ssd_step(pool, x, dt, a, b, c, d, fresh, **{by: key})

    ma = jax.jit(step, donate_argnums=(0,)).trace(
        sds(pool, jnp.float32), *_ssd_args(sds, (32,)),
        sds((32,), jnp.bool_), key).lower(
        lowering_platforms=("tpu",)).compile().memory_analysis()
    assert ma.alias_size_in_bytes == int(np.prod(pool)) * 4
    if by == "live":
        assert ma.temp_size_in_bytes < 8e6


def test_relu2_dense_arm_compiles_for_v5e_at_32_rows_of_16_experts(one_chip):
    """``dropless_ffn``'s dense arm on the cell's decode shape: 32 rows, 16
    held experts of 2688 x 1856 out of 128 routed, 6 a token."""
    import paddle_tpu
    from paddle_tpu.incubate.distributed.models.moe import moe_layer
    from paddle_tpu.jit.api import _Swap

    sds = lambda sh, dt: jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
    bf = jnp.bfloat16
    assert moe_layer.dropless_arm(32) == "dense"

    with paddle_tpu.LazyGuard():            # shapes only: no 319 MB drawn
        experts = moe_layer.Relu2ExpertFFN(16, 2688, 1856, dtype="bfloat16")

    def ffn(tokens, idx, gates, w_up, w_down):
        with _Swap([experts.w_up, experts.w_down], [w_up, w_down]):
            return moe_layer.dropless_ffn(tokens, idx, gates, experts,
                                          first=0)

    compiled = jax.jit(ffn).trace(
        sds((32, 2688), bf), sds((32, 6), jnp.int32),
        sds((32, 6), jnp.float32), sds((16, 2688, 1856), bf),
        sds((16, 1856, 2688), bf)).lower(
        lowering_platforms=("tpu",)).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes >= 2 * 16 * 2688 * 1856 * 2
    assert ma.temp_size_in_bytes < 64e6


@pytest.mark.parametrize("rows,V", [(24, 92544), (64, 65536), (32, 16384)],
                         ids=["chat-batch", "chat-batch-64",
                              "chat-short-batch-32"])
def test_sampler_compiles_for_v5e_as_two_loops_and_no_sort(rows, V, one_chip):
    """``sample_rows`` at the serving cells' shapes: the two searches stay
    loops (32 and ``(V - 1).bit_length()`` trips, not unrolled) and nothing
    is sorted (the sort alone took 13-22 s to compile, PR 39)."""
    from paddle_tpu.models.generation_utils import fold_keys, sample_rows

    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    text = jax.jit(lambda lg, seeds, pos, t, p, k: sample_rows(
        lg, fold_keys(seeds, pos), t, p, k)).trace(
        sds((rows, V), jnp.float32), sds((rows,), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.float32),
        sds((rows,), jnp.float32), sds((rows,), jnp.int32)).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert " sort(" not in text and text.count(" while(") == 2
