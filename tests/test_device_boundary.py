"""The device boundary fails loudly (docs: README "Running on the chip").

CPU-only pins for what ``chip_smoke.py`` proves on the chip: no entry point
reports a CPU run as a device result, the compile cache can be placed from
outside, and a worker that cannot get a backend says so at HELLO.
"""

import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    """No accelerator: non-zero exit naming the platform, before any model
    is built (the refusal is the first child's first act), and no result
    line on stdout."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert "platform is 'cpu', not 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout
    assert "losses" not in r.stdout         # never reached a train step


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    import jax

    from paddle_tpu.framework.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert enable_compile_cache() == "/some/dir"
    assert updates == []                    # jax reads the variable itself


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax

    from paddle_tpu.framework.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_bench_device_peak_unknown_kind_raises():
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert bench._device_peak(v5e) == 197e12
    for kind, platform in (("TPU v9", "tpu"), ("cpu", "cpu")):
        dev = types.SimpleNamespace(device_kind=kind, platform=platform)
        with pytest.raises(ValueError, match="no bf16 peak recorded"):
            bench._device_peak(dev)


def test_worker_without_backend_fails_at_hello_naming_cause(tmp_path):
    """A worker whose process cannot bring a backend up (on the chip: the
    driver holds it and the worker is not pinned to the cpu) fails the
    spawn at HELLO with the cause — not a bare "never said HELLO", and not
    after spawn_timeout_s."""
    from paddle_tpu.inference.procfleet import ProcReplica, WorkerSpec
    from paddle_tpu.inference.procfleet.proxy import WorkerDead

    spec = WorkerSpec(
        factory="paddle_tpu.inference.procfleet.presets:tiny_llama_engine",
        journal_path=str(tmp_path / "replica0.jrnl"),
        env={"JAX_PLATFORMS": "no_such_platform"})
    with pytest.raises(WorkerDead) as ei:
        ProcReplica(spec, idx=0, spawn_timeout_s=120.0)
    msg = str(ei.value)
    assert "failed before HELLO" in msg
    assert "no usable backend in the worker process" in msg
    assert "no_such_platform" in msg
