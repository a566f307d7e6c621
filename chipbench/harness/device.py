"""The device the run is on: refuse anything but the chips the cell asks
for, place the compile cache, count compilations, read memory."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(Exception):
    """No accelerator, an unknown kind, or too few chips: no result."""


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included: a hit still
    means a program the warm-up did not touch)."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.cache_misses = 0
        self.seconds = {}     # jax's own event name (last part) -> seconds
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        key = name.rsplit("/", 1)[-1]
        self.seconds[key] = self.seconds.get(key, 0.0) + secs

    def split(self) -> str:
        """Where jax's part of set-up went: tracing, lowering, compiling or
        loading from the cache."""
        names = {"jaxpr_trace_duration": "trace",
                 "jaxpr_to_mlir_module_duration": "lower",
                 "backend_compile_duration": "compile_or_load",
                 "cache_retrieval_time_sec": "cache_load"}
        return " ".join(f"{v}={self.seconds.get(k, 0.0):.1f}s"
                        for k, v in names.items())

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def place_compile_cache(rehearse: bool) -> str:
    """The program's own placement (``<checkout>/.jax_cache`` unless
    JAX_COMPILATION_CACHE_DIR names one), with every program cached however
    small or quick. A rehearsal on the CPU keeps no cache."""
    import jax

    if rehearse:
        return "off"
    from paddle_tpu.framework.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def peaks_of(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise NoChip(f"device_kind {kind!r} is not in chipbench/harness/"
                     f"peaks.json: a device without published peaks is an "
                     f"error, not a default")
    return table[kind]


def require(chips: int, rehearse: bool) -> dict:
    """What jax found, as the last line reports it; raises NoChip unless it
    is ``chips`` or more TPUs of a known kind (or a rehearsal)."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    if rehearse:
        if d0.platform != "cpu":
            raise NoChip("--rehearse is the CPU's: set JAX_PLATFORMS=cpu")
        info["peaks"] = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0,
                         "hbm_bytes": 1.0}
        return info
    if d0.platform != "tpu":
        raise NoChip(f"platform is {d0.platform!r}, not 'tpu': chipbench "
                     f"measures on the chip or not at all")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), jax found "
                     f"{len(devs)}")
    info["peaks"] = peaks_of(d0.device_kind)
    return info


def memory_peak(n_devices: int):
    """Peak on the fullest chip, and both of its parts in words: the live
    buffers' peak plus the peak of what the programs reserved for their
    temporaries (PERF.md section 7: either alone under-reports; together
    they come to the compiler's own count of the largest program). Both are
    peaks over the process's life so far. 0 where the backend reports
    nothing (CPU)."""
    import jax

    peak, words = 0, "nothing reported"
    for d in jax.devices()[:n_devices]:
        s = d.memory_stats() or {}
        live = int(s.get("peak_bytes_in_use", 0))
        temp = int(s.get("peak_bytes_reserved", 0))
        if live + temp > peak:
            peak = live + temp
            words = (f"peak_bytes_in_use {live} + peak_bytes_reserved {temp}"
                     f" of bytes_limit {s.get('bytes_limit', 0)}; "
                     f"bytes_in_use now {s.get('bytes_in_use', 0)}")
    return peak, words
