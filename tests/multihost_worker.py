"""Worker for test_multihost: one of N real jax processes forming ONE global
mesh (reference analogue: test/legacy_test/test_dist_base.py:1209 _run_cluster
— per-rank workers rendezvous and all-reduce genuinely different data).

Launched by the driver with the reference launch env contract
(PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / MASTER_ADDR / MASTER_PORT);
init_parallel_env maps it to jax.distributed.initialize.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def main():
    import paddle_tpu.distributed as dist

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    world = int(os.environ["PADDLE_TRAINERS_NUM"])

    dist.init_parallel_env()
    assert jax.process_count() == world, jax.process_count()
    assert jax.process_index() == rank
    assert dist.get_rank() == rank and dist.get_world_size() == world

    # ONE global mesh over every process's devices (2 local x N processes)
    devs = np.array(jax.devices())
    assert len(devs) == 2 * world
    mesh = Mesh(devs, ("dp",))

    # genuinely different per-rank operands: each local shard holds its
    # GLOBAL device index; psum must see all of them
    n_dev = len(devs)
    local_devs = [d for d in devs if d.process_index == rank]
    shards = [jax.device_put(np.full((1, 4), d.id, np.float32), d)
              for d in local_devs]
    global_arr = jax.make_array_from_single_device_arrays(
        (n_dev, 4), NamedSharding(mesh, P("dp")), shards)

    @jax.jit
    def reduce_all(x):
        return jax.shard_map(
            lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
            in_specs=P("dp"), out_specs=P("dp"))(x)

    out = reduce_all(global_arr)
    got = np.asarray(jax.device_get(
        out.addressable_shards[0].data)).reshape(-1)[0]
    want = float(sum(d.id for d in devs))
    assert got == want, (got, want)

    # the framework's collective API over an explicit global-mesh group
    g = dist.new_group(list(range(world)))
    assert g.nranks == world

    # eager framework all_reduce with genuinely different per-rank operands
    # (multi-process regime #3 in communication/functional.py)
    import paddle_tpu as paddle

    t = paddle.to_tensor(np.full((4,), float(rank + 1), np.float32))
    dist.all_reduce(t)
    want_ar = sum(range(1, world + 1))
    got_ar = float(np.asarray(t.numpy())[0])
    assert got_ar == want_ar, (got_ar, want_ar)

    # ---- eager SUBGROUP collectives (VERDICT r2 #9) ----
    # STRICT-subset subgroup when world >= 3: ranks [0, 1] reduce over a
    # 2-process submesh while rank 2 does not participate at all — the real
    # submesh-computation path (only shard-owning processes call in)
    if world >= 3:
        if rank in (0, 1):
            gsub = dist.new_group([0, 1])
            ts = paddle.to_tensor(np.full((3,), float(100 * (rank + 1)),
                                          np.float32))
            dist.all_reduce(ts, group=gsub)
            got_strict = float(np.asarray(ts.numpy())[0])
            assert got_strict == 300.0, got_strict

    # explicit full-membership group: every member calls in
    g2 = dist.new_group(list(range(world)))
    t2 = paddle.to_tensor(np.full((3,), float(10 * (rank + 1)), np.float32))
    dist.all_reduce(t2, group=g2)
    want_sub = sum(10 * (r + 1) for r in range(world))
    got_sub = float(np.asarray(t2.numpy())[0])
    assert got_sub == want_sub, (got_sub, want_sub)

    # singleton subgroup: each process reduces only with itself
    g_self = dist.new_group([rank])
    t3 = paddle.to_tensor(np.full((3,), float(rank + 7), np.float32))
    dist.all_reduce(t3, group=g_self)
    got_self = float(np.asarray(t3.numpy())[0])
    assert got_self == float(rank + 7), got_self

    # partial membership is a clear error, not a hang
    other = dist.new_group([(rank + 1) % world])
    t4 = paddle.to_tensor(np.ones((2,), np.float32))
    try:
        dist.all_reduce(t4, group=other)
        raise AssertionError("non-member all_reduce should have raised")
    except RuntimeError as e:
        assert "not a member" in str(e), e

    # NOTE: keep per-rank-varying values (got_self) out of this line — the
    # driver asserts the printed payload is identical across ranks
    print(f"MULTIHOST_OK rank={rank} sum={got} ar={got_ar} sub={got_sub}",
          flush=True)


if __name__ == "__main__":
    main()
