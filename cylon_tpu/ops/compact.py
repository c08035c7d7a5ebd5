"""Mask → index compaction with static output capacity.

The XLA replacement for the reference's dynamic builder loops (BooleanBuilder
mask + arrow Filter in Select, reference table_api.cpp:977-1005; index-vector
builds in the set ops): under jit, output shapes are static, so compaction
produces a fixed-capacity index vector padded with −1 plus a valid count.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Tuple

import jax
import jax.numpy as jnp

from ..analysis._abstract import is_abstract


def compact_indices(mask: jax.Array, size: int, fill: int = -1) -> jax.Array:
    """Indices of True entries in order, padded with ``fill`` to ``size``.

    Drop-in for ``jnp.flatnonzero(mask, size=, fill_value=)`` but via
    cumsum + one scatter — measured ~2x faster than XLA's flatnonzero
    lowering on TPU at multi-million-row sizes (the compaction is a hot
    step of every join/select/set-op kernel here).
    """
    n = mask.shape[0]
    pos = jnp.cumsum(mask) - 1
    tgt = jnp.where(mask, pos, size).astype(jnp.int32)
    return jnp.full((size,), fill, jnp.int32).at[tgt].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")


def mask_to_indices(mask: jax.Array, capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Indices of True entries, padded with −1 to ``capacity``; plus count."""
    idx = compact_indices(mask, capacity, fill=-1)
    return idx, jnp.sum(mask).astype(jnp.int32)


def pad_to(x: jax.Array, capacity: int, fill=0) -> jax.Array:
    """Right-pad axis 0 to ``capacity`` with ``fill`` (static shape)."""
    n = x.shape[0]
    if n == capacity:
        return x
    if n > capacity:
        raise ValueError(f"cannot pad length {n} into capacity {capacity}")
    pad_width = [(0, capacity - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill)


def update_size_hint(hints: dict, key, need: tuple,
                     shrink_after: int = 3) -> None:
    """Grow-fast / shrink-slow policy for optimistic-dispatch size hints
    (``need`` is a tuple of size classes, compared component-wise).

    Growing immediately is mandatory (an undersized hint forces a redo
    every call); shrinking only after ``shrink_after`` consecutive smaller
    observations keeps alternating small/large workloads from paying a
    wasted full dispatch on every large call.
    """
    cur = hints.get(key)
    if cur is None:
        hints[key] = (tuple(need), 0)
        return
    cv = cur[0]
    if any(n > c for n, c in zip(need, cv)):
        hints[key] = (tuple(max(n, c) for n, c in zip(need, cv)), 0)
        return
    if tuple(need) == cv:
        hints[key] = (cv, 0)
        return
    streak = cur[1] + 1
    hints[key] = ((tuple(need), 0) if streak >= shrink_after
                  else (cv, streak))


def hint_value(hints: dict, key):
    cur = hints.get(key)
    return None if cur is None else cur[0]


def optimistic_dispatch(hints: dict, key, dispatch, cnt_dev, post):
    """The optimistic two-phase pattern shared by shuffle and join:

    1. if a hint exists, ``dispatch(hint_sizes)`` immediately (device work
       starts while the host still waits on the counts);
    2. read ``cnt_dev`` (the device-side count array) and derive
       ``need = post(counts)`` — the bucketed size tuple actually required;
    3. redo ``dispatch(need)`` on a miss or any undersized component —
       this validation is what makes the optimism safe (an undersized
       dispatch would have produced truncated output);
    4. record the observation (grow-fast / shrink-slow).

    Returns ``(result, used_sizes, counts_or_None)``.

    **Deferred mode** (inside ``deferred_region``, with a hint available):
    step 2-4 are queued instead of executed — the host never blocks here.
    ``flush_pending()`` later performs ONE batched ``device_get`` for every
    queued count (one device→host round trip instead of one per op,
    measured ~7x cheaper than sequential reads) and reports whether every hinted
    dispatch was correctly sized; a caller that sees ``False`` must replay
    the region (``run_pipeline`` automates this).  The returned counts are
    ``None`` in deferred mode.
    """
    if is_abstract(cnt_dev):
        # abstract plan run (analysis/plan_check.py): the counts exist
        # only as shapes, so size the dispatch from zeroed counts — any
        # size-class is equally valid for shape/dtype checking, and
        # post() still runs so its contract checks see a clean header.
        # Hints are left untouched: a plan run must not steer the sizes
        # of later REAL dispatches.
        import numpy as np

        counts = np.zeros(cnt_dev.shape, cnt_dev.dtype)
        need = tuple(post(counts))
        return dispatch(need), need, counts
    _abort_if_poisoned()  # don't pile device work onto a doomed attempt
    hint = hint_value(hints, key)
    if hint is not None:
        # fault point (docs/robustness.md): an installed FaultPlan may
        # shrink the hint, forcing the undersized-dispatch validation /
        # replay machinery to run.  An undersized hint is always safe —
        # steps 2-3 below (or the deferred flush) detect and redo it —
        # and the hints dict itself is never polluted (update_size_hint
        # records the TRUE need).
        from .. import faults
        hint = faults.perturb("compact.hint", hint)
    if hint is not None and _deferred.depth > 0:
        result = dispatch(hint)
        _deferred.pending.append((hints, key, hint, cnt_dev, post))
        return result, hint, None
    if _deferred.depth > 0:
        # no hint ⇒ we must block on the count; resolve queued upstream
        # validations first — a count computed downstream of an undersized
        # dispatch must never size a dispatch or feed the hints
        flush_pending()
        _abort_if_poisoned()
    result = dispatch(hint) if hint is not None else None
    counts = _read_counts(cnt_dev)
    need = tuple(post(counts))
    if hint is None or any(n > h for n, h in zip(need, hint)):
        result = dispatch(need)
        used = need
    else:
        used = hint
    update_size_hint(hints, key, need)
    return result, used, counts


def _read_counts(cnt_dev):
    import jax
    import numpy as np

    from .. import faults, resilience, trace

    def attempt():
        faults.check("compact.read_counts")
        return np.asarray(jax.device_get(cnt_dev))

    # one blocking count read (sync-floor unit).  The read is
    # side-effect-free, so a transient transfer failure (a transfer
    # blip, injected chaos) is safely re-tried
    with trace.host_read():
        return resilience.retry_call(attempt, point="compact.read_counts")


class _DeferredState(threading.local):
    def __init__(self):
        self.depth = 0
        self.pending = []
        self.ok = True
        self.flushing = False


_deferred = _DeferredState()


def in_flush() -> bool:
    """True while flush_pending_with is walking queued posts — a post
    that wants to signal a degraded dispatch (shuffle's over-budget
    path) must not raise from inside the batch walk; it calls
    :func:`invalidate_flush` instead and the region replays."""
    return _deferred.flushing


def invalidate_flush() -> None:
    """Fail the current flush/region WITHOUT marking downstream counts
    poisoned: the dispatch that calls this was correctly SIZED (its
    outputs and every downstream count are valid) but should not have
    run — shuffle's over-budget case, where the replay must re-enter
    through the degraded path.  Later queued posts still validate; the
    region's flush returns False and ``run_pipeline`` replays.  Outside
    a deferred region this is a no-op by construction: region entry
    resets the flag and ``_abort_if_poisoned`` only fires at depth > 0."""
    _deferred.ok = False


class ReplayNeeded(Exception):
    """Raised at a host boundary inside a deferred region once an
    optimistic dispatch is known to have been undersized: everything
    downstream of it computed on truncated data, so continuing the attempt
    would consume poisoned counts (a zero-filled exchange can explode a
    join count toward cap² — an OOM-scale allocation).  ``run_pipeline``
    catches this, corrects the hints recorded so far, and replays."""


def _abort_if_poisoned() -> None:
    if _deferred.depth > 0 and not _deferred.ok:
        raise ReplayNeeded()


def deferred_mode() -> bool:
    return _deferred.depth > 0


@contextlib.contextmanager
def deferred_region():
    """Queue optimistic-dispatch validations instead of blocking per op.

    On exit the caller must ``flush_pending()`` and replay the region if it
    returns False (see ``run_pipeline``).  The reference analogue: Cylon's
    AllToAll is fully asynchronous with completion checked by a progress
    loop (reference net/ops/all_to_all.cpp isComplete); here the 'progress
    loop' collapses into one batched count read at the end of the region.
    """
    _deferred.depth += 1
    if _deferred.depth == 1:
        _deferred.ok = True
    try:
        yield
    except BaseException:
        if _deferred.depth == 1:
            # don't leak this region's queued validations into later
            # flushes (they would pin device buffers and force a
            # spurious replay of an unrelated pipeline)
            _deferred.pending.clear()
        raise
    finally:
        _deferred.depth -= 1
        if _deferred.depth == 0:
            # a failed attempt must not leak ok=False to depth 0: later
            # flush_pending() calls outside any region (and DTable.head's
            # not-ok branch) would observe a stale failure
            _deferred.ok = True


def flush_pending() -> bool:
    """Resolve every queued validation with one batched host read.

    Returns True when all hinted dispatches since the last flush were
    correctly sized (accumulated into the region-level flag).  Hints are
    updated for the trusted prefix only — entries queued after the first
    undersized dispatch carry poisoned counts, so their posts are skipped
    entirely and the replay re-validates them on sound inputs.
    """
    ok, _ = flush_pending_with(())
    return ok


def flush_pending_with(extra):
    """``flush_pending`` + fetch ``extra`` device arrays in the SAME batched
    ``device_get`` — one round trip covers both the queued validations and
    a caller's payload (e.g. a head() result).  Returns (ok, extra_values).
    """
    import jax
    import numpy as np

    batch = _deferred.pending
    _deferred.pending = []
    if not batch and not extra:
        return _deferred.ok, []
    from .. import faults, resilience, trace

    def attempt():
        faults.check("compact.flush")
        return jax.device_get([cnt for _, _, _, cnt, _ in batch]
                              + list(extra))

    with trace.host_read():  # ONE batched read for the whole flush
        values = resilience.retry_call(attempt, point="compact.flush")
    # Entries queue in dispatch order, so everything after the first
    # undersized dispatch computed on truncated inputs — its counts are
    # poisoned (a zero-filled exchange can explode a downstream join
    # count toward cap², and a contract-validating post would raise a
    # spurious hard error on the garbage) — skip their posts entirely;
    # the replay re-dispatches and re-validates them on sound inputs.
    # The failing entry itself is trustworthy: its count came from
    # inputs that validated.
    trusted = _deferred.ok
    _deferred.flushing = True
    try:
        for (hints, key, hint, _, post), v in zip(batch, values):
            if not trusted:
                continue
            need = tuple(post(np.asarray(v)))
            update_size_hint(hints, key, need)
            if any(n > h for n, h in zip(need, hint)):
                _deferred.ok = False
                trusted = False
    finally:
        _deferred.flushing = False
    return _deferred.ok, values[len(batch):]


def run_pipeline(fn, max_attempts: int = 3):
    """Run ``fn()`` (a pure pipeline of distributed ops) with deferred
    capacity validation; replay on an undersized optimistic dispatch.

    ``fn`` must be re-runnable: it may not mutate external state based on
    exported values (the standard shape — build DTables, chain dist ops,
    export at the end — satisfies this).  Steady state is one batched
    count read per pipeline instead of one blocking read per op.

    Observability (docs/robustness.md): every replayed attempt bumps
    ``pipeline.replays``; exhausting ``max_attempts`` bumps
    ``pipeline.fallback_plain`` and WARNS loudly before the plain-mode
    (per-op validated) fallback runs — a pipeline thrashing replays on
    every call used to be completely invisible.
    """
    from .. import trace
    for _ in range(max_attempts):
        try:
            with deferred_region():
                out = fn()
                ok = flush_pending()
        except ReplayNeeded:
            # a host boundary detected the undersize mid-attempt
            trace.count("pipeline.replays")
            continue
        if ok:
            return out
        trace.count("pipeline.replays")
    trace.count("pipeline.fallback_plain")
    from .. import logging as glog
    glog.warning(
        "run_pipeline: %d deferred attempt(s) all required replay — "
        "falling back to plain per-op validation for this run.  Hints "
        "were corrected along the way; if this warning recurs on every "
        "call, the workload's sizes oscillate faster than the grow-fast/"
        "shrink-slow hint policy converges (docs/robustness.md).",
        max_attempts)
    return fn()  # hints now corrected; plain mode validates per op


def next_bucket(n: int, minimum: int = 1024) -> int:
    """Round a dynamic size up to a quarter-step size-class bucket
    (2^k · {4,5,6,7}/4 — ≤25% padding overhead vs ≤100% for pure powers
    of two; gathers into the capacity buffer are the join's dominant cost).

    Bounds re-JIT count when materializing data-dependent shapes
    (SURVEY.md §7 hard part 1: capacity buffers + size-class bucketing).
    """
    cap = max(int(n), minimum)
    pow2 = 1 << (cap - 1).bit_length()
    for num in (5, 6, 7):  # 2^(k-1)·{1.25, 1.5, 1.75}
        q = (pow2 // 8) * num
        if q >= cap:
            return q
    return pow2
