"""The main path's kernels compiled for a described v5e:2x2 (no chip).

The TPU compiler is installed here and compiles for a chip that is
described, not attached.  It refuses what the chip would refuse — a
Pallas block that does not tile, a program over HBM, a program it cannot
partition — at no chip time.  Nothing runs: these tests prove that the
programs compile (and that the Pallas kernel and the all_to_all are in
them), not that their results are right.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under xdist only the
worker given this file should.  The persistent compilation cache is off
around these compiles (an entry written without a chip cannot be read
back).  x64 is off, as on the chip.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cylon_tpu._jax_compat import enable_x64
from cylon_tpu.context import MESH_AXIS
from cylon_tpu.ops import compact
from cylon_tpu.parallel import dist_ops, shuffle

ROWS = 4_000_000   # bench.py's TPU size: rows per device per side
CAP = compact.next_bucket(ROWS, minimum=8)


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # graftlint: ok[broad-except] — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with enable_x64(False):
        yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def mesh1(topo):
    return Mesh(np.array(topo.devices[:1]), (MESH_AXIS,))


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), (MESH_AXIS,))


def _sds(shape, dtype, mesh):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P(MESH_AXIS)))


def test_pallas_hash_compiles_at_bench_size(topo):
    from jax.sharding import SingleDeviceSharding
    from cylon_tpu.ops.hash_pallas import partition_ids_fused
    x = jax.ShapeDtypeStruct((CAP,), jnp.int32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    f = jax.jit(lambda k: partition_ids_fused((k,), (None,), 1000,
                                              interpret=False))
    assert "tpu_custom_call" in f.lower(x).compile().as_text()


def test_hash_partition_program_on_four_chips(mesh4):
    # the shuffle's partition-id program as dist_ops builds it on a TPU
    # mesh: the compiled Pallas kernel inside shard_map
    fn = dist_ops._hash_pids_fn.__wrapped__(mesh4, MESH_AXIS, CAP, 4, True)
    c = fn.lower(_sds((4,), jnp.int32, mesh4),
                 (_sds((4 * CAP,), jnp.int32, mesh4),), (None,)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_partition_counts_compile_without_a_scatter(mesh4):
    # phase 1 of the exchange at bench size: the per-target counts are a
    # compare-and-reduce, not bincount's 35-40 ms scatter-add
    fn = shuffle._counts_fn.__wrapped__(mesh4, MESH_AXIS, 4)
    hlo = fn.lower(_sds((4 * CAP,), jnp.int32, mesh4)).compile().as_text()
    # op names only: the metadata quotes Python frames, this test's too
    assert not re.search(r"\bscatter\(%", hlo)
    assert re.search(r"\breduce\(%", hlo)


def test_all_to_all_exchange_on_four_chips(mesh4):
    # the single-shot exchange of a 4-column side at bench size
    block = compact.next_bucket(int(ROWS / 4 * 1.05), minimum=8)
    fn = shuffle._exchange_fn.__wrapped__(mesh4, MESH_AXIS, 4, block, CAP)
    leaves = ((_sds((4 * CAP,), jnp.int32, mesh4),)
              + (_sds((4 * CAP,), jnp.float32, mesh4),) * 3)
    c = fn.lower(_sds((4 * CAP,), jnp.int32, mesh4), leaves).compile()
    assert "all-to-all" in c.as_text()
    per_device = c.memory_analysis().temp_size_in_bytes
    assert per_device < 16e9


def test_local_sort_join_plan_at_bench_size(mesh1):
    # phase 1 of the local join (the fused single-sort plan) over 4M+4M
    # rows, as dist_join builds it, with one payload leaf per side: the
    # TPU sort's compile time grows with its operand count, barely with
    # its length, so this still proves the bench size compiles and fits
    fn = dist_ops._join_phase1_fn.__wrapped__(mesh1, MESH_AXIS, "inner",
                                              "sort", False)
    cnt = _sds((1,), jnp.int32, mesh1)
    k = _sds((CAP,), jnp.int32, mesh1)
    leaves = ((k, None),)
    c = fn.lower(cnt, cnt, (k,), (None,), (k,), (None,),
                 leaves, leaves).compile()
    assert c.memory_analysis().temp_size_in_bytes < 16e9
