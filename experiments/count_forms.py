"""Time ways of counting partition ids into a few bins on one chip.

    python experiments/count_forms.py        # needs a TPU; prints JSON

Forms: ``jnp.bincount`` (a scatter-add), one ``sum(ids == t)`` per bin
(``shuffle._count_targets``, what the exchange runs), a broadcast
``[bins, n]`` compare reduced along the ids, and the ids reshaped to
``[n/128, 128]`` first.  Each runs K times inside one
``fori_loop`` whose carry feeds the next call (so nothing is hoisted);
the time per call is the difference of two K over the difference of
the K, as in ``profile_join.py``.  Every form is checked against
``np.bincount`` on the same ids.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cylon_tpu.parallel.shuffle import _count_targets

CAP = 4_194_304   # one shard's partition ids in the 4-chip join benchmark


def bincount(ids, n):
    return jnp.bincount(ids, length=n)


def broadcast(ids, n):
    return jnp.sum(ids[None, :] == jnp.arange(n, dtype=ids.dtype)[:, None],
                   axis=1, dtype=jnp.int32)


def lanes(ids, n):
    r = ids.reshape(-1, 128)
    return jnp.sum(r[None] == jnp.arange(n, dtype=ids.dtype)[:, None, None],
                   axis=(1, 2), dtype=jnp.int32)


FORMS = {"bincount": bincount, "per_bin": _count_targets,
         "broadcast": broadcast, "lanes": lanes}


def _looped(f, n, k):
    def run(ids):
        def body(_, c):
            # c[0] >= 0, so c[0] >> 31 is 0, but the compiler cannot know
            return c + f(ids + (c[0] >> 31), n).astype(jnp.int32)
        return jax.lax.fori_loop(0, k, body, jnp.zeros((n,), jnp.int32))
    return jax.jit(run)


def _best(g, ids, reps=3):
    jax.block_until_ready(g(ids))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(g(ids))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("count_forms.py times the chip; no TPU found")
    out = {"device": jax.devices()[0].device_kind, "ids": CAP}
    rng = np.random.default_rng(7)
    for n in (5, 257):
        host = rng.integers(0, n, CAP, dtype=np.int32)
        ids = jax.device_put(host)
        want = np.bincount(host, minlength=n)
        for name, f in FORMS.items():
            got = np.asarray(jax.jit(f, static_argnums=1)(ids, n))
            k0, k1 = (4, 44) if name == "bincount" else (10, 410)
            t0 = _best(_looped(f, n, k0), ids)
            t1 = _best(_looped(f, n, k1), ids)
            out[f"{name}.bins{n}"] = {
                "equal": bool((got == want).all()),
                "us_per_call": (t1 - t0) / (k1 - k0) * 1e6}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
