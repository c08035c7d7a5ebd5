"""What the window drivers share.

A traffic mix is a data file (``benchmarks/traffic/<mix>.json``) whose
``driver`` key names a window driver, ``benchmarks/drivers/<driver>.py``,
and whose other keys are that driver's parameters.  A driver file
defines ``Driver(spec, deployment, ctx, seed)`` with:

* ``warm()``: one pass of every shape the window uses (set-up);
* ``run(seconds) -> Window``: the measured window, inside a
  ``span("window")`` (a traced run reads the window from it), its
  end-to-end numbers in ``Window.e2e``;
* ``release()``: read back what the check needs, then drop the
  program's output;
* ``facts() -> dict``: sizes for the per-layer readers;
* ``check(ref_dir) -> dict``: the numbers compared with the limits.

Every driver wraps its host phases in ``bench.*`` profiler spans
(``span``), which label the idle gaps of a traced run.
"""
from __future__ import annotations

import math
import os
import pickle
import sys
from dataclasses import dataclass, field
from typing import Dict, List

from . import compare


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def block(table) -> None:
    import jax
    jax.block_until_ready([c.data for c in table.columns])


def warn(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Window:
    """What one measured window did, for the metrics and the check."""
    seconds: float = 0.0
    units: int = 0                 # streams, joins or requests completed
    attempted: int = 0
    failed: int = 0
    e2e: Dict[str, float] = field(default_factory=dict)
    queue_waits_ms: List[float] = field(default_factory=list)


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``%
    of the values at or below it."""
    v = sorted(values)
    return v[max(int(math.ceil(p / 100.0 * len(v))) - 1, 0)]


def cached_reference(cache_dir: str, key: str, fn):
    """``fn()``, kept on disk under ``cache_dir/key`` (this benchmark's
    own pickles only) so a second run on the same seed skips it."""
    path = os.path.join(cache_dir, key + ".pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    out = fn()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "wb") as f:
        pickle.dump(out, f)
    os.replace(path + ".tmp", path)
    return out


def tpch_check(dep, answers, ref_dir):
    """Each (query, frame) in ``answers`` against the reference."""
    from . import tpch_reference as ref
    queries = sorted({q for q, _ in answers})
    wants = {}
    data = None
    for q in queries:
        def compute(q=q):
            nonlocal data
            if data is None:
                data = dep.host_data(ref.inputs(queries))
            return ref.answer(q, data)
        wants[q] = cached_reference(ref_dir, f"seed{dep.seed}/{q}", compute)
    data = None
    numbers = {"wrong_rows": 0, "rel_err": 0.0}
    for q, got in answers:
        pairs = ref.COLUMNS[q]
        g = got[[a for a, _ in pairs]].rename(columns=dict(pairs))
        numbers = compare.merge(numbers, compare.compare_answer(
            g, wants[q], ref.KEYS[q]))
    return numbers
