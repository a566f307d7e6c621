"""RPC + parameter-server tests.

Reference model: test/legacy_test rpc tests (multi-process, env-var contract)
and PS push/pull semantics of ps/table. Here: two real processes rendezvous
through TCPStore, exchange RPCs, and run a PS train loop.
"""

import multiprocessing as mp
import os
import pickle
import socket
import time

import numpy as np
import pytest

from paddle_tpu.distributed.communication.store import TCPStore
from paddle_tpu.distributed.ps import ParameterServer
from paddle_tpu.distributed.ps._tables import DenseTable, SparseTable


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# tables (pure host logic)
# ---------------------------------------------------------------------------

def test_dense_table_sgd():
    t = DenseTable([4], optimizer="sgd", lr=0.1)
    t.push(np.ones(4, np.float32))
    np.testing.assert_allclose(t.pull(), -0.1 * np.ones(4), rtol=1e-6)


def test_sparse_table_lazy_rows_adagrad():
    t = SparseTable(8, optimizer="adagrad", lr=0.1)
    rows = t.pull([3, 7])
    assert rows.shape == (2, 8)
    g = np.ones((2, 8), np.float32)
    t.push([3, 7], g)
    after = t.pull([3, 7])
    # adagrad first step: -lr * g / (|g| + eps) ~ -0.1
    np.testing.assert_allclose(after - rows, -0.1, rtol=1e-3)
    assert t.stat()["rows"] == 2


def test_parameter_server_local():
    ps = ParameterServer()
    ps.create_dense_table("w", [3], optimizer="sgd", lr=0.5)
    ps.push_dense("w", np.array([1.0, 2.0, 3.0], np.float32))
    np.testing.assert_allclose(ps.pull_dense("w"), [-0.5, -1.0, -1.5])
    ps.create_sparse_table("emb", 4)
    v = ps.pull_sparse("emb", [10, 20])
    assert v.shape == (2, 4)


def test_parameter_server_concurrent_handlers_exact():
    """PT-RACE-002 regression (tools/lint_concurrency.py): ParameterServer
    methods execute on rpc handler threads — create-if-absent races and
    unguarded table lookups must stay exact under concurrency (the table
    lock + locked ``_table`` lookup). Every push lands exactly once."""
    import threading

    ps = ParameterServer()
    n_threads, n_pushes = 8, 50
    errs = []

    def handler(t):
        try:
            for i in range(n_pushes):
                # racing create-or-validate: same config is idempotent
                ps.create_dense_table("w", [4], optimizer="sgd", lr=1.0)
                ps.create_sparse_table("emb", 4, lr=1.0)
                ps.push_dense("w", np.ones(4, np.float32))
                ps.push_sparse("emb", [t], np.ones((1, 4), np.float32))
                ps.stat()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=handler, args=(t,), daemon=True)
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    total = n_threads * n_pushes
    # sgd with lr=1.0: value == -sum(grads) exactly, so a lost push shows
    np.testing.assert_allclose(ps.pull_dense("w"),
                               np.full(4, -float(total), np.float32))
    assert ps.stat()["emb"]["rows"] == n_threads


# ---------------------------------------------------------------------------
# rpc across real processes
# ---------------------------------------------------------------------------

def _sq(x):
    return x * x


def _rpc_worker(rank, world, port, q):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(world)
    from paddle_tpu.distributed import rpc

    try:
        rpc.init_rpc(f"worker{rank}", rank, world, f"127.0.0.1:{port}")
        if rank == 0:
            out = rpc.rpc_sync("worker1", _sq, args=(7,))
            fut = rpc.rpc_async("worker1", _sq, args=(9,))
            infos = rpc.get_all_worker_infos()
            q.put(("ok", out, fut.result(timeout=30), [w.name for w in infos]))
        else:
            time.sleep(2.0)  # stay alive to serve
        rpc.shutdown()
    except Exception as e:  # pragma: no cover
        q.put(("err", repr(e), None, None))


def test_rpc_two_processes():
    port = _free_port()
    ctx = mp.get_context("fork")
    q = ctx.Queue()
    procs = [ctx.Process(target=_rpc_worker, args=(r, 2, port, q))
             for r in range(2)]
    for p in procs:
        p.start()
    status, out, fut_out, names = q.get(timeout=60)
    for p in procs:
        p.join(timeout=30)
    assert status == "ok", out
    assert out == 49 and fut_out == 81
    assert names == ["worker0", "worker1"]


# ---------------------------------------------------------------------------
# full PS train loop across processes: server + trainer
# ---------------------------------------------------------------------------

def _ps_role(rank, port, q):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    from paddle_tpu.distributed import ps, rpc

    try:
        if rank == 0:
            ps.run_server()     # before it can be reached: a trainer may
                                # call as soon as init_rpc's rendezvous ends
        rpc.init_rpc("ps0" if rank == 0 else f"trainer{rank}", rank, 2,
                     f"127.0.0.1:{port}")
        if rank == 0:
            time.sleep(4.0)  # serve
        else:
            w = ps.PsWorker("ps0")
            w.create_dense_table("w", [2], optimizer="sgd", lr=0.1)
            rng = np.random.default_rng(0)
            w_true = np.array([1.5, -2.0], np.float32)
            loss = None
            for _ in range(60):
                wv = w.pull_dense("w")
                x = rng.standard_normal((16, 2)).astype(np.float32)
                err = x @ wv - x @ w_true
                loss = float((err ** 2).mean())
                grad = 2 * x.T @ err / len(x)
                w.push_dense("w", grad)
            # sparse path through rpc too
            w.create_sparse_table("emb", 4)
            rows = w.pull_sparse("emb", [1, 2, 3])
            w.push_sparse("emb", [1, 2, 3], np.ones((3, 4), np.float32))
            q.put(("ok", loss, w.pull_dense("w"), rows.shape))
        rpc.shutdown()
    except Exception as e:  # pragma: no cover
        q.put(("err", repr(e), None, None))


def test_ps_train_loop_two_processes():
    port = _free_port()
    ctx = mp.get_context("fork")
    q = ctx.Queue()
    procs = [ctx.Process(target=_ps_role, args=(r, port, q)) for r in range(2)]
    for p in procs:
        p.start()
    status, loss, w_final, emb_shape = q.get(timeout=90)
    for p in procs:
        p.join(timeout=30)
    assert status == "ok", loss
    assert loss < 0.05, f"PS training did not converge: {loss}"
    np.testing.assert_allclose(w_final, [1.5, -2.0], atol=0.15)
    assert emb_shape == (3, 4)


# ---------------------------------------------------------------------------
# sharded PS: 2 servers + 1 trainer, feature ids sharded fid % n_servers
# ---------------------------------------------------------------------------

def _sharded_role(rank, port, q):
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    from paddle_tpu.distributed import ps, rpc

    try:
        name = f"ps{rank}" if rank < 2 else "trainer"
        if rank < 2:
            ps.run_server()     # before it can be reached (see _ps_role)
        rpc.init_rpc(name, rank, 3, f"127.0.0.1:{port}")
        if rank < 2:
            time.sleep(5.0)  # serve
        else:
            c = ps.ShardedPsClient(["ps0", "ps1"])
            c.create_sparse_table("emb", 4, optimizer="adagrad", lr=0.5)
            ids = [0, 1, 2, 3, 4, 5, 6, 7]
            rows0 = c.pull_sparse("emb", ids)
            # async push + barrier, then pull back: every row moved
            c.push_sparse_async("emb", ids, np.ones((8, 4), np.float32))
            c.wait()
            rows1 = c.pull_sparse("emb", ids)
            moved = np.abs(rows1 - rows0).sum(axis=1)
            # shard placement: each server holds only its fid % 2 rows
            stats = c.stat()
            counts = (stats["ps0"]["emb"]["rows"],
                      stats["ps1"]["emb"]["rows"])
            # dense table lands on exactly one server
            c.create_dense_table("w", [2], lr=0.1)
            c.push_dense("w", np.asarray([1.0, -1.0], np.float32))
            wv = c.pull_dense("w")
            q.put(("ok", moved.tolist(), counts, wv.tolist()))
        rpc.shutdown()
    except Exception as e:  # pragma: no cover
        q.put(("err", repr(e), None, None))


def test_sharded_ps_three_processes():
    """ShardedPsClient (round 5): sparse ids fan out across TWO server
    processes (fid % n_servers, per-shard rpc_async + reassembly in request
    order), async-push barrier works, and dense tables land on exactly one
    shard — the reference's brpc PS sharding scheme at small scale."""
    port = _free_port()
    ctx = mp.get_context("fork")
    q = ctx.Queue()
    procs = [ctx.Process(target=_sharded_role, args=(r, port, q))
             for r in range(3)]
    for p in procs:
        p.start()
    status, moved, counts, wv = q.get(timeout=90)
    for p in procs:
        p.join(timeout=30)
    assert status == "ok", moved
    assert all(m > 0 for m in moved), f"some rows never updated: {moved}"
    assert counts == (4, 4), f"shard row counts wrong: {counts}"
    np.testing.assert_allclose(wv, np.asarray([-0.1, 0.1]), atol=1e-5)
