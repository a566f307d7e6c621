"""Kernels of the serving path compiled for a described TPU v5e, at real
widths, with no chip attached: Mosaic refuses here what it would refuse on
the chip (a slice off the tiling, too much VMEM, an op it cannot lower),
which interpret mode never sees. Nothing runs, so nothing here says a
result is right or fast.

The topology is described inside a fixture, never at import: only the
worker that runs this file may load the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.paged_attention import paged_decode_attention


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
]


@pytest.mark.parametrize("shape", _PAGED_DECODE_SHAPES, ids=lambda s: s[0])
def test_paged_decode_compiles_for_v5e(shape, one_chip, monkeypatch):
    _, b, hq, hkv, d, page, maxp, n_pages, dtype = shape
    # the dispatch guard asks for the backend; steer it here, not by an option
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    lowered = jax.jit(paged_decode_attention).trace(
        sds((b, hq, d), dtype), sds((n_pages, hkv, page, d), dtype),
        sds((n_pages, hkv, page, d), dtype), sds((b, maxp), jnp.int32),
        sds((b,), jnp.int32)).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == 1, "one Pallas call, no fallback"
    assert "pt_paged_decode" in text
    lowered.compile()
