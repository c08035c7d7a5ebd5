"""The exchange's per-target row counts: exact, and without a scatter.

Every exchange counts its shard's partition ids per target shard before
it sizes and sends anything.  ``jnp.bincount`` did that as a combining
scatter-add; ``shuffle._count_targets`` does it as a compare-and-reduce.
These tests hold the counts to ``bincount``'s answer bit for bit (the
padding id ``P`` that marks dropped rows included) and check that no
counting scatter is left in the lowered programs.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cylon_tpu.context import MESH_AXIS
from cylon_tpu.parallel import shuffle

CAP = 1000   # rows per shard: not a multiple of the 128-lane tile


def _mesh(nparts):
    return Mesh(np.array(jax.devices("cpu")[:nparts]), (MESH_AXIS,))


def _shard_pids(case, nparts, rng):
    """[nparts, CAP] partition ids, ``nparts`` being the dropped-row id:
    random, or with the first shard all padding, or with the last shard
    sending every row to one target."""
    pids = rng.integers(0, nparts + 1, (nparts, CAP), dtype=np.int32)
    if case == "all_padding":
        pids[0] = nparts
    elif case == "one_target":
        pids[-1] = rng.integers(0, nparts)
    return pids


@pytest.mark.parametrize("case", ["random", "all_padding", "one_target"])
@pytest.mark.parametrize("nparts", [1, 2, 3, 4, 8])
def test_counts_fn_matches_bincount(nparts, case):
    rng = np.random.default_rng(1000 * nparts + len(case))
    pids = _shard_pids(case, nparts, rng)
    mesh = _mesh(nparts)
    pid = jax.device_put(pids.reshape(-1),
                         NamedSharding(mesh, P(MESH_AXIS)))
    got = np.asarray(shuffle._counts_fn(mesh, MESH_AXIS, nparts)(pid))
    want = np.stack([np.bincount(row, minlength=nparts + 1)[:nparts]
                     for row in pids])
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("length", [1, 2, 5, 9, 65, 257])
def test_count_targets_matches_bincount(length):
    # ids below 0 and at or above length included: the helper must treat
    # them exactly as bincount does (clipped to bin 0 / counted nowhere)
    rng = np.random.default_rng(length)
    ids = rng.integers(-3, length + 4, 4099, dtype=np.int32)
    got = jax.jit(shuffle._count_targets, static_argnums=1)(
        jnp.asarray(ids), length)
    assert got.dtype == jnp.int32 and got.shape == (length,)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.bincount(ids, length=length)))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.bincount(np.maximum(ids, 0), minlength=length + 4)[:length])


def _combining_scatters(stablehlo: str) -> int:
    """Scatter ops whose update region adds (a bincount's scatter-add);
    the exchange's compaction scatter only sets."""
    n = 0
    for m in re.finditer(r'"stablehlo\.scatter"', stablehlo):
        region = stablehlo[m.end():stablehlo.find("})", m.end())]
        n += "stablehlo.add" in region
    return n


def test_counting_lowers_without_a_scatter():
    mesh = _mesh(4)
    pid = jax.ShapeDtypeStruct((4 * CAP,), jnp.int32)
    counts = shuffle._counts_fn.__wrapped__(mesh, MESH_AXIS, 4)
    assert "scatter" not in counts.lower(pid).as_text()

    leaves = (jax.ShapeDtypeStruct((4 * CAP,), jnp.int32),
              jax.ShapeDtypeStruct((4 * CAP,), jnp.float32))
    exchange = shuffle._exchange_fn.__wrapped__(mesh, MESH_AXIS, 4, 512, CAP)
    assert _combining_scatters(exchange.lower(pid, leaves).as_text()) == 0
    # the detector sees the scatter-add that bincount lowers to
    old = jax.jit(lambda i: jnp.bincount(i, length=5)).lower(
        jax.ShapeDtypeStruct((CAP,), jnp.int32)).as_text()
    assert _combining_scatters(old) == 1
