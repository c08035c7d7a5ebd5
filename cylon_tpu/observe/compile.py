"""Compilation observability — stable program names and measured builds.

The engine's kernel factories (``shuffle._exchange_fn`` and friends)
are ``lru_cache``-keyed builders of jit/shard_map programs: a factory
cache hit is free, a miss builds a NEW program whose first dispatch
pays trace + XLA compile.  That cost is the latency floor's missing
denominator (docs/tpu_perf_notes.md "the per-query floor"): ROADMAP §5's
q11-at-0.23x number is meaningless until compile time, retrace storms
and kernel time can be told apart.  This module is the instrument:

  * :func:`named_jit` — ``jax.jit`` under a stable, readable program
    name derived from the factory that builds it (``shuffle._exchange_fn``
    compiles as ``jit(shuffle__exchange_fn)`` and runs in the profiler
    trace as module ``jit_shuffle__exchange_fn``), so device time can be
    read by factory.  The name is fixed when the program is built; a
    dispatch pays nothing for it.  Every factory and hand-rolled program
    cache builds through it (graftlint ``program-unnamed``).

  * :func:`kernel_factory` — a drop-in replacement for
    ``functools.lru_cache(maxsize=None)`` on kernel factories.  Factory
    hits/misses tally ``compile.cache_hits`` / ``compile.cache_misses``,
    and the factory names the programs it builds.

  * compiles counted where they happen: one ``jax.monitoring`` listener
    on JAX's backend-compile event counts EVERY XLA compile (or
    persistent-cache load) — ``compile.builds`` + ``compile.build_us``,
    a ``compile.build`` span whose args carry the program name and
    compile-ms — and attributes it to the thread's active per-query
    collector (the serving layer and EXPLAIN ANALYZE each open one, so
    ``QueryHandle.compile_ms`` and ``report.totals["compile_ms"]`` are
    exact, not inferred).  Abstract plan runs (analysis/plan_check)
    compile nothing, so they count nothing.

  * the **recompile-storm detector**: each factory keeps a sliding
    window of recent distinct cache keys; when one factory builds
    :data:`STORM_KEYS` distinct keys within :data:`STORM_WINDOW_S`
    seconds, a ``glog.warn_once`` fires NAMING the key component that
    varies (the factory's parameter name + the run of values), and
    ``compile.storms`` tallies.  A shuffle whose size classes thrash,
    or a predicate rebuilt per call defeating the select cache, becomes
    one loud line instead of a mystery wall-clock tax.
"""
from __future__ import annotations

import functools
import inspect
import os
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

from .locks import OrderedLock

__all__ = ["kernel_factory", "named_jit", "program_names",
           "attribute_compiles", "note_build", "clear_state", "serial_call",
           "enable_compile_cache", "STORM_KEYS", "STORM_WINDOW_S"]

# the storm detector's window: this many DISTINCT cache keys built by
# one factory within this many seconds is a retrace storm worth a warn
STORM_KEYS = 8
STORM_WINDOW_S = 30.0

# how much of a cache key / key component run the warn line renders
_KEY_REPR_LEN = 120


_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# builds shorter than this are not cached: the suite's CPU compiles are
# mostly about that long, the chip's sort programs far longer
CACHE_MIN_COMPILE_SECS = 0.5


def compile_cache_dir() -> str:
    """The persistent compilation cache's directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed in-checkout
    ``<repo>/.jax_cache`` (git-ignored), never a path relative to the
    working directory, which would move and never find what an earlier
    run wrote."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache in
    ``compile_cache_dir()`` and no other; return that directory.  Every
    entry point (bench, chip_smoke, tests, experiments) calls this, and
    child processes get the same placement through the environment
    (tests/test_examples.py)."""
    import jax

    cache = compile_cache_dir()
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_SECS)
    return cache


# ---------------------------------------------------------------------------
# per-query attribution (the serving layer / ANALYZE open a collector)
# ---------------------------------------------------------------------------

_tls = threading.local()


@contextmanager
def attribute_compiles():
    """Collect every XLA compile on this thread inside the block; yields
    the (live) list of ``{"program", "compile_ms"}`` dicts.  Nests like
    ``stats.collect_digests``: the innermost collector owns the events
    of its extent.  Zero overhead per compile when no collector is open
    beyond one thread-local read."""
    _ensure_listener()
    stack = getattr(_tls, "collectors", None)
    if stack is None:
        stack = _tls.collectors = []
    out: List[Dict[str, Any]] = []
    stack.append(out)
    try:
        yield out
    finally:
        stack.pop()


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event: str, duration: float, **kw) -> None:
    """The ``jax.monitoring`` listener: one XLA compile (or persistent
    cache load) just finished on this thread.  JAX calls it on the
    thread that compiles, which is the thread that dispatched."""
    if event != BACKEND_COMPILE_EVENT:
        return
    from .. import trace
    stack = getattr(_tls, "collectors", None)
    if not stack and not trace.counters_enabled():
        return
    ms = duration * 1e3
    program = kw.get("fun_name")
    trace.count("compile.builds")
    trace.count("compile.build_us", int(round(ms * 1e3)))
    trace.record_span("compile.build", time.perf_counter() - duration, ms,
                      args={"program": program,
                            "compile_ms": round(ms, 3)})
    if stack:
        stack[-1].append({"program": program, "compile_ms": ms})


_listener_lock = threading.Lock()
_listening = False


def _ensure_listener() -> None:
    """Register :func:`_on_compile` with ``jax.monitoring``, once per
    process."""
    global _listening
    if _listening:
        return
    with _listener_lock:
        if _listening:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listening = True


# ---------------------------------------------------------------------------
# stable program names
# ---------------------------------------------------------------------------

# label -> origin ("module.factory") of every program named so far: a
# label names one origin, so the device trace's module names say which
# factory built each program
_program_origins: Dict[str, str] = {}
_names_lock = threading.Lock()


def _label(origin: str) -> str:
    """``"shuffle._exchange_fn"`` -> ``"shuffle__exchange_fn"``: the
    jitted function's name, which XLA's module name carries."""
    return re.sub(r"[^A-Za-z0-9_]", "_", origin)


def program_names() -> Dict[str, str]:
    """{program label: origin} of every program :func:`named_jit` has
    named in this process."""
    with _names_lock:
        return dict(_program_origins)


def named_jit(fn, name: Optional[str] = None, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under a stable program name.

    ``name`` is ``"<module>.<function>"`` of the code that builds the
    program; inside a :func:`kernel_factory` build it defaults to the
    factory's own (``"shuffle._exchange_fn"``).  The traced function is
    renamed before jit wraps it, so the name is fixed at build time and
    a dispatch (jit's C++ fast path) never calls back into it.  One name
    belongs to one origin: a second origin under the same label raises.
    """
    if name is None:
        building = getattr(_tls, "building", None)
        if not building:
            raise ValueError("named_jit outside a kernel_factory build "
                             "needs name='<module>.<function>'")
        name = building[-1]
    label = _label(name)
    with _names_lock:
        seen = _program_origins.setdefault(label, name)
    if seen != name:
        raise ValueError(f"program name {label!r} is taken by {seen!r}; "
                         f"{name!r} needs another")

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = label
    import jax
    return jax.jit(program, **jit_kwargs)


# ---------------------------------------------------------------------------
# recompile-storm detection (factory-level, on cache misses)
# ---------------------------------------------------------------------------

# Lint contract (graftlint shared-state-unguarded,
# docs/static_analysis.md "Concurrency discipline"): writes to these
# module registries hold the mapped lock.
GUARDED_STATE = {"_recent_keys": "_storm_lock",
                 "_program_origins": "_names_lock",
                 "_listening": "_listener_lock"}

_storm_lock = OrderedLock("compile.storm")
_recent_keys: Dict[str, deque] = {}   # factory -> deque[(t, key)]


def _differing_components(keys, params: Tuple[str, ...]) -> str:
    """Name the cache-key component(s) that vary across ``keys`` —
    ``block=64/128/256/…`` reads at the parameter level the factory
    author thinks in, not as opaque tuples."""
    keys = [k for k in keys if isinstance(k, tuple)]
    if not keys or len({len(k) for k in keys}) != 1:
        return "heterogeneous keys"
    parts = []
    for i in range(len(keys[0])):
        vals = []
        for k in keys:
            v = repr(k[i])
            if v not in vals:
                vals.append(v)
        if len(vals) <= 1:
            continue
        name = params[i] if i < len(params) else f"arg{i}"
        run = "/".join(sorted(vals)[:6])
        if len(vals) > 6:
            run += f"/… ({len(vals)} values)"
        parts.append(f"{name}={run}"[:_KEY_REPR_LEN])
    return ", ".join(parts) if parts else "identical keys re-built"


def note_build(factory: str, key: Tuple,
               params: Tuple[str, ...] = ()) -> None:
    """Record one factory cache MISS into the storm window (and tally
    it); fires the storm warning when the window fills with distinct
    keys.  Public so non-factory caches (a hand-rolled builder) can feed
    the same detector."""
    from .. import trace
    trace.count("compile.cache_misses")
    now = time.monotonic()
    with _storm_lock:
        dq = _recent_keys.setdefault(factory, deque())
        dq.append((now, key))
        while dq and now - dq[0][0] > STORM_WINDOW_S:
            dq.popleft()
        distinct = {k for _, k in dq}
    if len(distinct) < STORM_KEYS:
        return
    from .. import logging as glog
    fired = glog.warn_once(
        ("compile.storm", factory),
        "recompile storm: factory %s built %d distinct programs within "
        "%.0f s — differing key component(s): %s. Every build pays trace "
        "+ XLA compile; a thrashing key component usually means an "
        "unquantized size or an identity-keyed callable rebuilt per "
        "call (docs/observability.md \"compile tracking\"). "
        "(warned once per factory per session)",
        factory, len(distinct), STORM_WINDOW_S,
        _differing_components(distinct, params))
    if fired:
        # one DETECTION per factory per session (warn_once's first-fire
        # return) — not one bump per miss while the window stays full,
        # which would read a single storm as dozens
        trace.count("compile.storms")


def clear_state() -> None:
    """Forget the storm windows (test isolation).  Factory caches and
    per-kernel seen-signature sets are untouched — compiled programs
    stay compiled."""
    with _storm_lock:
        _recent_keys.clear()


# ---------------------------------------------------------------------------
# host-platform dispatch serialization
# ---------------------------------------------------------------------------
#
# XLA's CPU client rendezvouses collective participants in-process: a
# shard_map launch blocks inside dispatch until every virtual-device
# participant has arrived.  Two kernels launched from different Python
# threads at the same time can interleave their per-device arrivals
# across each other's rendezvous and starve both — on a single-core
# host the interleaving is near-certain and the launch blocks forever
# (observed: concurrent ``replicate_table`` / concurrent serve submits
# hang tier-1 until pytest's global timeout).  Real accelerator
# platforms serialize launches on the device stream and are unaffected,
# so the lock is gated to the cpu backend.  Serialized issuance is
# exactly what a single-threaded caller does anyway; the RLock keeps
# nested kernel calls on one thread legal, and uncontended acquisition
# costs nanoseconds.

# An OrderedLock (reentrant, matching the RLock it replaced) so the
# serialization pressure is visible: ``lock.held_us`` watermarks how
# long launches waited behind one another, the acquire counter sizes
# the contention, and a hang under the lock shows up in the flight
# recorder via the hold-time watchdog — the recompile-storm / hang
# triage used to be blind to exactly this lock.
_dispatch_lock = OrderedLock("compile.dispatch", reentrant=True)
_serialize_dispatch: Optional[bool] = None


def _serial_dispatch() -> bool:
    global _serialize_dispatch
    if _serialize_dispatch is None:
        try:
            import jax
            _serialize_dispatch = jax.default_backend() == "cpu"
        except Exception:  # graftlint: ok[broad-except] — the gate is
            _serialize_dispatch = False  # best-effort; never break dispatch
    return _serialize_dispatch


def serial_call(fn, args, kwargs):
    """Invoke ``fn`` with cpu-backend launch serialization (module
    comment above).  Dispatch alone is not enough: jit dispatch is
    async, so two serially-ISSUED programs can still execute — and
    rendezvous — concurrently.  The lock is therefore held until the
    outputs are ready, guaranteeing at most one program in flight.
    Under an ambient abstract trace nothing executes, so nothing is
    held.  ``_KernelHandle`` routes every wrapped kernel through here;
    bare ``lru_cache`` factories whose kernels are reachable from
    worker threads (``dtable._head_fn`` and friends) call it directly."""
    if not _serial_dispatch():
        return fn(*args, **kwargs)
    from .._jax_compat import trace_state_clean
    if not trace_state_clean():
        return fn(*args, **kwargs)
    with _dispatch_lock:
        out = fn(*args, **kwargs)
        try:
            # the block IS the point: at most one program in flight on
            # the cpu backend (module comment) — the sanctioned
            # blocking-under-lock site the rule exists to make loud
            jax.block_until_ready(out)  # graftlint: ok[blocking-call-under-lock]
        except Exception:  # graftlint: ok[broad-except] — non-array
            pass           # leaves in the output tree stay un-waited
        return out


# ---------------------------------------------------------------------------
# the factory decorator
# ---------------------------------------------------------------------------

class _KernelHandle:
    """Wraps one built kernel: transparent call-through, serialized on
    the cpu backend (:func:`serial_call`)."""

    __slots__ = ("_fn", "fresh")

    def __init__(self, fn) -> None:
        self._fn = fn
        self.fresh = True

    def __call__(self, *args, **kwargs):
        return serial_call(self._fn, args, kwargs)


@contextmanager
def _building_as(name: str):
    """Name the programs :func:`named_jit` builds on this thread inside
    the block after factory ``name`` (nested factory builds shadow)."""
    stack = getattr(_tls, "building", None)
    if stack is None:
        stack = _tls.building = []
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


def kernel_factory(fn):
    """``functools.lru_cache(maxsize=None)`` for kernel factories, plus
    compile observability (module docstring).  Drop-in: same positional
    hashable-args contract, ``cache_clear``/``cache_info`` preserved;
    graftlint's ``kernel-factory-unkeyed`` rule recognizes it as a cache
    decorator.  Programs the factory builds with :func:`named_jit` are
    named ``<module>.<factory>``."""
    factory = fn.__qualname__
    name = f"{fn.__module__.rsplit('.', 1)[-1]}.{factory}"
    try:
        params = tuple(inspect.signature(fn).parameters)
    except (TypeError, ValueError):
        params = ()
    _ensure_listener()

    @functools.wraps(fn)
    def build(*key):
        with _building_as(name):
            return fn(*key)

    @functools.lru_cache(maxsize=None)
    def _build(*key) -> _KernelHandle:
        return _KernelHandle(build(*key))

    @functools.wraps(fn)
    def wrapper(*key):
        handle = _build(*key)
        if handle.fresh:
            handle.fresh = False
            note_build(factory, key, params)
        else:
            from .. import trace
            trace.count("compile.cache_hits")
        return handle

    wrapper.cache_clear = _build.cache_clear
    wrapper.cache_info = _build.cache_info
    # the uncached factory call (programs still named after the factory)
    wrapper.__wrapped__ = build
    return wrapper
