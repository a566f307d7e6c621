"""Replica worker: one spawned process owning one ServingSupervisor.

``worker_main`` is the spawn target (docs/SERVING.md "Process fleet" state
machine: spawn → hello → serve → drain → reap). The worker

- builds a :class:`~paddle_tpu.inference.recovery.ServingSupervisor` from
  the spec's picklable engine factory (its OWN model, its OWN device
  memory — process-per-replica is what makes replica death process death),
- journals to the driver-shared on-disk path in the UNCHANGED
  ``RequestJournal`` format — the driver's journal-backed failover reads a
  SIGKILL'd worker's journal exactly like an in-process replica's,
- serves the PT-PROC message loop over a localhost socket
  (procfleet/wire.py), single-threaded by design: the supervisor, engine
  and journal are only ever touched from this loop,
- exposes its own :class:`~paddle_tpu.observability.MetricsServer` on an
  ephemeral port, reported in its HELLO — the driver aggregates every
  worker's ``/metrics`` under ``replica=i`` labels
  (docs/OBSERVABILITY.md remote-scrape topology).

Failure posture: a supervisor step that raises past its recovery budget is
replica death — the worker sends a typed ERROR, abandons (no journal
flush beyond what the flush barrier already guaranteed) and exits nonzero;
the driver fails its work over from the on-disk journal. A SIGKILL skips
even the ERROR — the driver sees the stream close (``WireClosed``) and
takes the same path.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import os
import pickle
import socket
import sys
from typing import Callable, Dict, List, Optional, Tuple, Union

from .transport import TcpTransport, Transport
from .wire import Message, WireClosed, WireCorrupt

__all__ = ["WorkerSpec", "resolve_factory", "worker_main",
           "worker_thread_main"]

#: idempotence-key dedup depth: a duplicated/retried delivery arrives
#: within one op window of the original, so a small bounded cache is the
#: whole contract (the JOURNAL carries single-serve across crashes; this
#: carries it across the wire)
_IDEM_CACHE = 128


@dataclasses.dataclass
class WorkerSpec:
    """Everything a worker process needs to become a serving replica.

    - ``factory``: engine factory the CHILD imports — a module-level
      callable (pickled by reference) or a ``"module:qualname"`` string;
      called with ``factory_kwargs`` and must return a
      ``ContinuousBatchingEngine``. Factories seed their own rng so every
      replica builds bit-identical weights (procfleet/presets.py).
    - ``journal_path``: the driver-shared on-disk journal (the SAME
      ``replica{i}.g{gen}.jrnl`` naming the in-process fleet uses).
    - ``sup_kwargs``: forwarded to ``ServingSupervisor`` (step_budget_s,
      max_recoveries, fsync, watchdog_grace_steps).
    - ``metrics_port``: 0 binds an ephemeral port (reported in HELLO);
      ``None`` disables the worker's metrics endpoint.
    - ``env``: extra environment applied before heavy imports
      (e.g. ``JAX_PLATFORMS=cpu`` to pin workers to host devices).
    - ``tier``: informational tag echoed in telemetry.
    - ``mesh``: in-replica tensor-parallel width — the worker builds its
      engine with ``MeshConfig(tp=mesh)`` over its own device group, so
      fleet scale-out composes with in-replica sharding (docs/SERVING.md
      "Sharded serving"). Spawned workers own a fresh runtime: on cpu
      platforms the worker forces ``mesh`` XLA host devices before the
      backend initializes; accelerator platforms bind their visible
      devices.
    - ``device_group``: explicit device indices (into the worker
      runtime's ``jax.devices()``) for the mesh — loopback worker
      threads share ONE process runtime, so the driver hands each
      replica a disjoint slice; None = the first ``mesh`` devices.
    """

    factory: Union[str, Callable]
    journal_path: str
    factory_kwargs: dict = dataclasses.field(default_factory=dict)
    sup_kwargs: dict = dataclasses.field(default_factory=dict)
    metrics_port: Optional[int] = 0
    env: dict = dataclasses.field(default_factory=dict)
    tier: str = "serving"
    mesh: Optional[int] = None
    device_group: Optional[Tuple[int, ...]] = None
    #: worker-side KV-chain verification (KVChainCodec(verify_crc=...)).
    #: False is the net_flaky_migration drill's control arm: what a
    #: checksum-less transfer does to bitflipped migration bytes
    verify_crc: bool = True


def resolve_factory(spec: WorkerSpec) -> Callable:
    fac = spec.factory
    if isinstance(fac, str):
        mod, _, qual = fac.partition(":")
        if not mod or not qual:
            raise ValueError(
                f"factory reference {fac!r} must be 'module:qualname'")
        obj = importlib.import_module(mod)
        for part in qual.split("."):
            obj = getattr(obj, part)
        fac = obj
    if not callable(fac):
        raise TypeError(f"worker factory {fac!r} is not callable")
    kwargs = dict(spec.factory_kwargs)
    if spec.mesh:
        # bind this replica's device group and shard the engine over it
        # (MeshConfig is built HERE, in the worker runtime — device
        # handles don't pickle across the spawn boundary)
        import jax

        from ..serving import MeshConfig

        tp = int(spec.mesh)
        devs = jax.devices()
        idxs = (list(spec.device_group) if spec.device_group is not None
                else list(range(min(tp, len(devs)))))
        if len(idxs) < tp or any(int(i) >= len(devs) for i in idxs):
            raise ValueError(
                f"worker mesh tp={tp} wants device group {idxs} but this "
                f"runtime has {len(devs)} devices")
        kwargs["mesh"] = MeshConfig(
            tp=tp, devices=[devs[int(i)] for i in idxs])

        def build(mesh_tp: Optional[int] = tp):
            # width-aware factory: the elastic supervisor's PT-SRV-008
            # degrade rebuilds at the widest SURVIVING width — a prefix
            # of this worker's device group — or unsharded (mesh_tp
            # None) when no narrower width divides the head counts
            # (docs/RESILIENCE.md "Elastic serving mesh")
            kw = dict(kwargs)
            if mesh_tp is None:
                kw["mesh"] = None
            elif int(mesh_tp) != tp:
                kw["mesh"] = MeshConfig(
                    tp=int(mesh_tp),
                    devices=[devs[int(i)] for i in idxs[:int(mesh_tp)]])
            return fac(**kw)

        return build
    return lambda: fac(**kwargs)


def _engine_hello(engine) -> dict:
    """The geometry the driver-side proxy mirrors as ``.engine`` (the
    surface FleetRouter reads: page_size for prefix-chain keys, max_batch/
    max_queue for the brownout depth default) plus the pool shape the
    tiered router's migration pre-check needs."""
    out = {"page_size": int(engine.page_size),
           "max_batch": int(engine.max_batch),
           "max_queue": (None if engine.max_queue is None
                         else int(engine.max_queue)),
           "max_len": int(engine.max_len),
           "prefix_cache": engine.prefix_cache is not None,
           # in-replica mesh width (1 = unsharded): the proxy mirrors it,
           # the fleet collector labels per-device-group telemetry by it
           "mesh_tp": (int(engine.mesh.tp)
                       if getattr(engine, "mesh", None) is not None else 1)}
    if engine.prefix_cache is not None:
        kv = engine.caches["kv"]
        # the first layer that keeps K and V (a state layer has no heads)
        pair = next(e for e in kv if isinstance(e, tuple))
        kvh, page, hd = (int(d) for d in pair[0].shape[1:])
        out.update(layers=len(kv), kvh=kvh, hd=hd,
                   dtype=str(pair[0].dtype), maxp=int(engine._maxp),
                   num_blocks=int(engine._alloc.num_blocks))
    return out


class _WorkerLoop:
    """The serve loop, factored for testability (handlers take/return
    Messages; ``worker_main`` owns the socket + process lifecycle)."""

    def __init__(self, sup, registry=None, verify_crc: bool = True):
        self.sup = sup
        self.registry = registry
        self.draining = False
        self.verify_crc = bool(verify_crc)
        # rid -> tokens already wired, for OPEN rids only: entries are
        # pruned when the done update ships (or the rid withdraws /
        # migrates out), so the per-step scan is O(live), not O(lifetime)
        # — same discipline recovery.py's _sync_progress documents
        self._sent: Dict[int, int] = {}
        # idempotence keys already served -> their success reply. A
        # duplicated or retried SUBMIT/MIGRATE_IN is answered from here
        # without touching the supervisor: at-most-once ADMISSION per key
        # (the reply's piggybacked load may be stale; admission may not)
        self._idem: "collections.OrderedDict[str, Message]" = \
            collections.OrderedDict()
        self._codec = None
        # last mesh width reported to the driver: an elastic PT-SRV-008
        # degrade shrinks the engine's mesh IN PLACE (the worker absorbs
        # it and keeps serving) — the next TOKENS reply piggybacks the
        # new width, a "re-HELLO" without a reconnect, so the router
        # re-weights capacity instead of declaring the worker dead
        self._last_mesh_tp = self._engine_mesh_tp()

    def _engine_mesh_tp(self) -> int:
        eng = self.sup.engine
        return (int(eng.mesh.tp)
                if getattr(eng, "mesh", None) is not None else 1)

    # -- per-type handlers -------------------------------------------------
    def handle(self, msg: Message) -> Message:
        from ..serving import EngineSaturated, RequestShed

        try:
            fn = getattr(self, "_on_" + msg.mtype.lower())
        except AttributeError:
            return Message("ERROR", {
                "etype": "WireCorrupt",
                "msg": f"PT-PROC-001: {msg.mtype} is not a request the "
                       "worker serves"})
        try:
            return fn(msg)
        except (EngineSaturated, RequestShed, ValueError, KeyError) as e:
            # typed refusals: the proxy re-raises the named class — the
            # router's fall-through routing depends on the distinction
            return Message("ERROR", {"etype": type(e).__name__,
                                     "msg": str(e)})

    def _idem_hit(self, msg: Message) -> Optional[Message]:
        key = msg.payload.get("idem")
        cached = None if key is None else self._idem.get(key)
        if cached is None:
            return None
        # a fresh copy: the serve loop stamps each reply with ITS
        # request's _seq, and the cache must stay seq-free
        return Message(cached.mtype, dict(cached.payload), cached.blob)

    def _idem_store(self, msg: Message, reply: Message) -> None:
        key = msg.payload.get("idem")
        if key is None:
            return
        self._idem[key] = Message(reply.mtype, dict(reply.payload),
                                  reply.blob)
        while len(self._idem) > _IDEM_CACHE:
            self._idem.popitem(last=False)

    def _on_submit(self, msg: Message) -> Message:
        from ..recovery import _request_from
        from ..serving import EngineSaturated

        dup = self._idem_hit(msg)
        if dup is not None:
            return dup
        if self.draining and not msg.payload["resume"]:
            raise EngineSaturated(
                "worker is draining — new admissions refused (resumed/"
                "migrated work still lands)")
        user = _request_from(msg.payload["req"])
        delivered = [int(t) for t in msg.payload["delivered"]]
        if msg.payload["resume"]:
            user.output = list(delivered)
            user._n_out = len(delivered)
        self.sup.submit(user, resume=bool(msg.payload["resume"]))
        self._sent[user.rid] = len(delivered)
        reply = Message("SUBMITTED", {"rid": int(user.rid),
                                      "load": int(self.sup.load())})
        self._idem_store(msg, reply)
        return reply

    def _updates(self) -> List[dict]:
        ups = []
        for rid, sent in list(self._sent.items()):
            user = self.sup.requests.get(rid)
            if user is None:
                self._sent.pop(rid, None)
                continue
            new = user.output[sent:]
            if not new and not user.done:
                continue
            up = {"rid": int(rid), "toks": [int(t) for t in new],
                  "done": bool(user.done), "failed": bool(user.failed),
                  "error": user.error, "n_out": len(user.output)}
            if user.done:
                self._sent.pop(rid, None)   # terminal shipped: stop
                #                             tracking (O(live) scan)
            else:
                self._sent[rid] = len(user.output)
            ups.append(up)
        return ups

    def _behind(self) -> List[int]:
        return [int(rid) for rid in list(self.sup._live)
                if self.sup.behind(rid)]

    def _ready(self) -> List[int]:
        eng = self.sup.engine
        if eng.prefix_cache is None:
            return []
        return [int(rid) for rid in eng.migration_ready()
                if rid in self.sup._live and rid not in self.sup._verify]

    def _capacity(self) -> List[int]:
        """``[free_slots, optimistic free pages]`` for the tiered
        router's pre-handoff capacity gate (mirrors the in-process
        ``_compatible``: free + radix-registered is optimistic — the
        import's EngineSaturated fallback stays load-bearing)."""
        eng = self.sup.engine
        if eng.prefix_cache is None:
            return [0, 0]
        return [len(eng._free_slots),
                int(eng._alloc.free_blocks) + len(eng._radix)]

    def _on_step(self, msg: Message) -> Message:
        self.sup.step()
        payload = {
            "updates": self._updates(), "load": int(self.sup.load()),
            "sig": list(self.sup.progress()), "behind": self._behind(),
            "ready": self._ready(), "cap": self._capacity(),
            "has_work": bool(self.sup.has_work())}
        tp = self._engine_mesh_tp()
        if tp != self._last_mesh_tp:
            self._last_mesh_tp = tp
            payload["mesh_tp"] = tp
        return Message("TOKENS", payload)

    def _on_progress(self, msg: Message) -> Message:
        return Message("PROGRESS_REPLY", {
            "sig": list(self.sup.progress()), "load": int(self.sup.load()),
            "has_work": bool(self.sup.has_work()),
            "behind": self._behind()})

    def _on_withdraw(self, msg: Message) -> Message:
        rid = int(msg.payload["rid"])
        rec = self.sup.withdraw(rid)
        if rec is not None:
            self._sent.pop(rid, None)
        return Message("WITHDRAWN", {"rec": rec,
                                     "load": int(self.sup.load())})

    def _on_drain(self, msg: Message) -> Message:
        self.draining = True
        return Message("DRAINING", {"load": int(self.sup.load())})

    def _on_metrics(self, msg: Message) -> Message:
        text = "" if self.registry is None else self.registry.dump()
        return Message("METRICS_TEXT", {"text": text})

    def _on_shutdown(self, msg: Message) -> Message:
        return Message("BYE", {})

    # -- tiered migration (inference/disagg.py over the wire) --------------
    def _codec_(self):
        if self._codec is None:
            from ..disagg import KVChainCodec

            self._codec = KVChainCodec(verify_crc=self.verify_crc)
        return self._codec

    def _on_migrate_out(self, msg: Message) -> Message:
        rid = int(msg.payload["rid"])
        codec = self._codec_()
        # flush-before-surface, then export; retire ONLY once the bytes
        # are safely built — a failure above leaves the rid owned here
        self.sup._sync_progress()
        twin = self.sup._live.get(rid)
        if twin is None or twin.done:
            raise KeyError(f"rid {rid} is not exportable (done or gone)")
        art = codec.export_chain(self.sup.engine, rid)
        hdr = codec.peek(art)
        # wire everything the flush just surfaced BEFORE the chain leaves:
        # the driver's delivered prefix must equal the artifact's
        # (collected only once export cannot fail anymore — _updates()
        # advances the sent marks, so a later refusal would lose deltas)
        ups = self._updates()
        self.sup.retire_migrated(rid, hdr["digest"])
        self._sent.pop(rid, None)
        return Message("CHAIN", {"rid": rid, "digest": str(hdr["digest"]),
                                 "pages": int(hdr["n_written"]),
                                 "updates": ups},
                       blob=art)

    def _on_migrate_in(self, msg: Message) -> Message:
        from ..disagg import KVChainCorrupt
        from ..recovery import _request_from

        dup = self._idem_hit(msg)
        if dup is not None:
            return dup
        user = _request_from(msg.payload["req"])
        delivered = [int(t) for t in msg.payload["delivered"]]
        user.output = list(delivered)
        user._n_out = len(delivered)
        try:
            self.sup.submit_migrated(user, msg.blob, self._codec_())
        except KVChainCorrupt as e:
            return Message("ERROR", {"etype": "KVChainCorrupt",
                                     "msg": str(e)})
        self._sent[user.rid] = len(delivered)
        reply = Message("SPLICED", {"rid": int(user.rid)})
        self._idem_store(msg, reply)
        return reply

    def _on_migrate_cancel(self, msg: Message) -> Message:
        """Hedged migration's loser side: the driver placed this rid's
        chain elsewhere first. If the MIGRATE_IN actually landed here
        (the race's ambiguous outcome), retire it — journal ``migr-kv``,
        ACTIVE slot released, pages decref'd: the allocator is exactly
        where it was before the splice. Idempotent: an rid that never
        landed (or already left) rolls back nothing."""
        rid = int(msg.payload["rid"])
        twin = self.sup._live.get(rid)
        rolled = False
        if twin is not None and not twin.done:
            self.sup.retire_migrated(rid, str(msg.payload["digest"]))
            self._sent.pop(rid, None)
            rolled = True
        # the key that admitted it must not answer a later duplicate
        # with SPLICED for work this worker no longer owns
        for key in [k for k, v in self._idem.items()
                    if v.payload.get("rid") == rid]:
            self._idem.pop(key, None)
        return Message("CANCELLED", {"rid": rid,
                                     "rolled_back": rolled})


def _hello_msg(spec: WorkerSpec, sup, loop: _WorkerLoop,
               metrics_port: Optional[int]) -> Message:
    """The HELLO frame, including journal-restart pending work (a worker
    (re)started over a live journal replays it in the supervisor
    constructor): the reconstructed admits + delivered marks let the
    driver-side proxy own the caller-facing objects."""
    from ..recovery import _admit_record

    pending = []
    for rid, user in sup.requests.items():
        loop._sent[rid] = len(user.output)
        pending.append({"req": _admit_record(user),
                        "delivered": [int(t) for t in user.output]})
    return Message("HELLO", {
        "pid": int(os.getpid()), "metrics_port": metrics_port,
        "journal_path": str(spec.journal_path),
        "engine": dict(_engine_hello(sup.engine), tier=str(spec.tier),
                       pending=pending),
        "state": {"load": int(sup.load()),
                  "sig": list(sup.progress()),
                  "has_work": bool(sup.has_work()),
                  "cap": loop._capacity()}})


def _serve(tr: Transport, sup, loop: _WorkerLoop) -> int:
    """The message loop over any transport. Returns the worker's exit
    code: 0 = clean SHUTDOWN, 2 = driver gone / stream damaged, 3 =
    fatal handler failure (replica death). Codes 2/3 abandon the
    supervisor — no journal flush beyond what the flush barrier already
    guaranteed, exactly the recovery contract failover replays."""
    while True:
        try:
            msg = tr.recv_frame()
        except (WireClosed, WireCorrupt):
            # driver gone (or stream damaged — same retreat)
            sup.abandon()
            return 2
        if msg.mtype == "SHUTDOWN":
            sup.close()
            bye = Message("BYE", {})
            if "_seq" in msg.payload:
                bye.payload["_seq"] = msg.payload["_seq"]
            tr.send_frame(bye)
            return 0
        try:
            reply = loop.handle(msg)
        except Exception as e:  # noqa: BLE001 — replica death boundary
            # a step crash past the recovery budget (or any unexpected
            # handler failure): this replica is DEAD — tell the driver
            # why if the pipe still works, then exit without flushing
            try:
                tr.send_frame(Message(
                    "ERROR", {"etype": type(e).__name__,
                              "msg": f"worker fatal: {e}"}))
            except (WireClosed, WireCorrupt, OSError):
                pass
            sup.abandon()
            return 3
        # echo the request's sequence id: a driver that timed out and
        # retried matches replies to attempts and discards stale ones
        if "_seq" in msg.payload:
            reply.payload["_seq"] = msg.payload["_seq"]
        tr.send_frame(reply)


def _build_supervisor(spec: WorkerSpec):
    """Bring this process's backend up, then build the supervisor over it.
    The backend comes first and on its own so that a chip another process
    holds is reported as that, not as a failure somewhere inside the
    factory."""
    import jax

    from ...framework.compile_cache import enable_compile_cache
    from ..recovery import ServingSupervisor

    enable_compile_cache()
    try:
        jax.devices()
    except RuntimeError as e:
        raise RuntimeError(
            f"no usable backend in the worker process (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}): {e} — a chip "
            f"belongs to one process at a time; pin workers to the cpu "
            f"(ProcFleetConfig.env) or give each its own device") from e
    return ServingSupervisor(resolve_factory(spec), spec.journal_path,
                             **dict(spec.sup_kwargs))


def worker_main(spec_bytes: bytes, host: str, port: int) -> None:
    """Worker entry: connect back to the driver, build the supervisor,
    HELLO, serve until SHUTDOWN / driver loss / fatal supervisor error.
    Launched as ``python -m paddle_tpu.inference.procfleet.worker`` by
    :class:`~.proxy.ProcReplica` (a plain subprocess: no inherited
    interpreter state, no parent-__main__ re-execution — the child is
    exactly what production process isolation gives you)."""
    spec: WorkerSpec = pickle.loads(spec_bytes)
    for k, v in (spec.env or {}).items():
        os.environ[k] = str(v)
    if spec.mesh and int(spec.mesh) > 1 and spec.device_group is None:
        # mesh-sharded replica on host (cpu) devices: this fresh runtime
        # must expose tp devices, and XLA reads the flag at backend init
        # — force it BEFORE anything touches jax. Accelerator platforms
        # (no cpu pin) bind their own visible devices instead.
        if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count="
                    f"{int(spec.mesh)}").strip()
    sock = socket.create_connection((host, int(port)), timeout=30)
    sock.settimeout(None)
    tr = TcpTransport(sock=sock)
    server = None
    try:
        from paddle_tpu.observability import (MetricsRegistry, MetricsServer,
                                              retry_collector,
                                              supervisor_collector)

        try:
            sup = _build_supervisor(spec)
        except Exception as e:  # noqa: BLE001 — replica death boundary
            # no HELLO will come: say why instead, so the driver raises
            # the cause now rather than a bare "never said HELLO"
            tr.send_frame(Message("ERROR", {
                "etype": type(e).__name__, "msg": f"worker fatal: {e}"}))
            raise
        registry = MetricsRegistry()
        registry.register_collector(supervisor_collector(sup))
        registry.register_collector(retry_collector())
        g = registry.gauge("pt_procfleet_worker_up",
                           "1 while this worker process serves")
        g.set(1.0, tier=str(spec.tier))
        metrics_port = None
        if spec.metrics_port is not None:
            server = MetricsServer(registry, port=int(spec.metrics_port))
            metrics_port = server.port
        loop = _WorkerLoop(sup, registry, verify_crc=spec.verify_crc)
        tr.send_frame(_hello_msg(spec, sup, loop, metrics_port))
        code = _serve(tr, sup, loop)
        if code != 0:
            os._exit(code)
    finally:
        if server is not None:
            server.close()
        tr.close()
    sys.exit(0)


def worker_thread_main(spec: WorkerSpec, tr: Transport) -> None:
    """Loopback twin of :func:`worker_main`: the same supervisor, journal
    format, HELLO and serve loop, over an in-process
    :class:`~.transport.LoopbackTransport` on this thread — the fast arm
    for tests/drills that would otherwise pay a process spawn + cold jit
    per case. Differences are exactly the process boundary: ``spec.env``
    is NOT applied (one shared interpreter), there is no per-worker
    metrics server (the driver's registry already sees this process),
    and "process death" is the transport closing, which failover reads
    through the journal identically. Thread-safety: the supervisor,
    engine and journal are touched only from this thread — the serve
    loop is single-threaded by design, same as the process worker."""
    try:
        from ..recovery import ServingSupervisor

        build = resolve_factory(spec)
        sup = ServingSupervisor(build, spec.journal_path,
                                **dict(spec.sup_kwargs))
        loop = _WorkerLoop(sup, None, verify_crc=spec.verify_crc)
        tr.send_frame(_hello_msg(spec, sup, loop, None))
        _serve(tr, sup, loop)
    except (WireClosed, WireCorrupt):
        pass                    # driver closed while we were replying
    except Exception as e:  # noqa: BLE001 — replica death boundary
        # construction failed (bad factory, journal IO): tell the driver
        # like the process worker's fatal path would
        try:
            tr.send_frame(Message("ERROR", {
                "etype": type(e).__name__, "msg": f"worker fatal: {e}"}))
        except Exception:       # noqa: BLE001 — already dying
            pass
    finally:
        tr.close()


def _cli(argv: Optional[List[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="procfleet replica worker (spawned by ProcReplica)")
    ap.add_argument("--spec", required=True,
                    help="path to the pickled WorkerSpec")
    ap.add_argument("--host", required=True)
    ap.add_argument("--port", required=True, type=int)
    args = ap.parse_args(argv)
    with open(args.spec, "rb") as f:
        spec_bytes = f.read()
    worker_main(spec_bytes, args.host, args.port)


if __name__ == "__main__":
    _cli()
