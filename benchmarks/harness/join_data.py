"""Cylon's weak-scaling join tables, generated in HBM from a seed given
at run time, and their plain reference.

Each side has an int32 key ``k`` uniform in [0, 0.99 * rows) and three
float32 payloads ``v0..v2`` uniform in [0, 1) (Cylon's
``run_dist_scaling.py`` protocol: 4 columns, keys drawn from 99% of the
row count, rows fixed per worker).  The draws are the repo generator's
(``bench_join_cols``), with the seed traced: left uses ``seed``, right
``seed + 7919``.  Rows are block-distributed, ``rows_per_chip`` on each
chip of the mesh.
"""
from __future__ import annotations

import functools

import numpy as np

from .tpch_data import _mix, _randint, _salt, bucket

_TAG_K, _TAG_V = 60, 61
RIGHT_SEED_OFFSET = 7919


def _side_cols(jnp, seed, g, krange: int):
    out = {"k": _randint(jnp, _salt(jnp, seed, _TAG_K), g, krange)}
    for j in range(3):
        u = _mix(g.astype(jnp.uint32) + _salt(jnp, seed, _TAG_V + j), jnp)
        out[f"v{j}"] = ((u >> jnp.uint32(8)).astype(jnp.float32)
                        * jnp.float32(1.0 / (1 << 24)))
    return out


@functools.lru_cache(maxsize=None)
def _generator(rows_per_chip: int, chips: int, sharding):
    import jax
    import jax.numpy as jnp

    cap = bucket(rows_per_chip)
    krange = max(int(rows_per_chip * chips * 0.99), 1)

    def fn(seed_l, seed_r):
        p = jnp.arange(chips * cap, dtype=jnp.int32)
        shard, local = p // jnp.int32(cap), p % jnp.int32(cap)
        g = shard * jnp.int32(rows_per_chip) + local
        valid = local < rows_per_chip
        out = []
        for s in (seed_l, seed_r):
            cols = _side_cols(jnp, s, g, krange)
            out.append({k: jnp.where(valid, v, jnp.zeros((), v.dtype))
                        for k, v in cols.items()})
        return out

    return jax.jit(fn, out_shardings=sharding), cap


def generate(rows_per_chip: int, chips: int, seed: int, sharding):
    """(left columns, right columns, capacity per chip) on the mesh."""
    import jax.numpy as jnp
    fn, cap = _generator(rows_per_chip, chips, sharding)
    s = int(seed) % (1 << 32)
    left, right = fn(jnp.uint32(s),
                     jnp.uint32((s + RIGHT_SEED_OFFSET) % (1 << 32)))
    return left, right, cap


def device_tables(ctx, left, right, cap: int, rows_per_chip: int):
    import jax
    from cylon_tpu.dtypes import DataType, Type
    from cylon_tpu.parallel import DColumn, DTable
    chips = ctx.get_world_size()
    out = []
    for cols in (left, right):
        dcols = [DColumn("k", DataType(Type.INT32), cols["k"])]
        dcols += [DColumn(f"v{j}", DataType(Type.FLOAT), cols[f"v{j}"])
                  for j in range(3)]
        counts = jax.device_put(np.full(chips, rows_per_chip, np.int32),
                                ctx.sharding())
        dt = DTable(ctx, dcols, cap, counts)
        dt.counts_host()
        out.append(dt)
    return out


def host_side(cols, cap: int, rows_per_chip: int, chips: int):
    """One generated side read back as a pandas frame (valid rows)."""
    import jax
    import pandas as pd
    got = jax.device_get(cols)
    return pd.DataFrame({k: np.asarray(v).reshape(chips, cap)
                         [:, :rows_per_chip].reshape(-1)
                         for k, v in got.items()})


def reference(ldf, rdf, control: bool = False):
    """The inner join on ``k`` in pandas, in the program's output column
    order.  ``control``: payloads rounded to bfloat16, the next
    precision below the float32 the configuration states."""
    import pandas as pd
    if control:
        import ml_dtypes
        bf = lambda df: df.assign(**{  # noqa: E731
            c: df[c].to_numpy().astype(ml_dtypes.bfloat16).astype(np.float32)
            for c in ("v0", "v1", "v2")})
        ldf, rdf = bf(ldf), bf(rdf)
    m = ldf.merge(rdf, on="k", how="inner", suffixes=("_l", "_r"))
    return pd.DataFrame({"lt-k": m["k"], "lt-v0": m["v0_l"],
                         "lt-v1": m["v1_l"], "lt-v2": m["v2_l"],
                         "rt-k": m["k"], "rt-v0": m["v0_r"],
                         "rt-v1": m["v1_r"], "rt-v2": m["v2_r"]})
