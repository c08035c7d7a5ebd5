"""meshprobe — measure what each collective actually costs on THIS mesh.

The exchange cost model (parallel/cost.py) ranks strategies on
(rounds, wire bytes): a good proxy, but a proxy — arXiv:2112.01075's
point is that the right collective SEQUENCE depends on the topology,
and a topology is known only by measurement.  This module is the
measurement: a startup microbench times the three collective primitives
every exchange lowering is built from — ``lax.all_to_all`` (single-shot
+ chunked rounds), ``lax.ppermute`` (the staged ring) and
``lax.all_gather`` (replicate-and-filter + the broadcast replica) — at
a few payload sizes on the LIVE mesh, and least-squares fits each to
the classic α/β model::

    t(wire_bytes) = latency_s + wire_bytes / bytes_per_s

The fitted coefficients are cached **per mesh fingerprint** (device
set + axis name), optionally persisted via ``CYLON_MESHPROBE_PATH``,
and surfaced through ``cost.predicted_ms`` so EXPLAIN / EXPLAIN ANALYZE
annotates every exchange with predicted-vs-observed ms
(docs/observability.md "the mesh bandwidth profile").

The coefficients are REPORTED, not steering: the chooser keeps ranking
on (rounds, wire) unless the escape hatch ``CYLON_COST_MEASURED=1`` /
``config.set_cost_measured(True)`` flips it to rank feasible strategies
by predicted time — the A/B lever for validating the proxy against the
measurement before any future PR lets measurements steer by default.

Probing is always EXPLICIT (``probe(ctx)``) — it dispatches collectives
and hard-syncs, which a latency-sensitive path must never do by
surprise; ``get_profile(ctx)`` is the read side and never probes.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from .._jax_compat import shard_map
from jax.sharding import PartitionSpec as P

from .. import trace
from ..observe.compile import named_jit

__all__ = ["MeshProfile", "mesh_fingerprint", "probe", "get_profile",
           "put_profile", "clear_profiles", "COLLECTIVES", "TRANSFERS"]

COLLECTIVES = ("all_to_all", "ppermute", "all_gather")

# host-boundary transfer probes (docs/out_of_core.md "staging price
# math"): the two legs every staged-spill lowering pays — host→device
# (jax.device_put under the mesh sharding) and device→host
# (jax.device_get) — fitted to the same α/β model and cached under the
# same mesh fingerprint, so cost.predicted_ms can price a spill's PCIe
# round trips next to its ICI rounds
TRANSFERS = ("h2d", "d2h")

# fingerprint -> MeshProfile (plus the optional JSON mirror behind
# CYLON_MESHPROBE_PATH); lock-guarded — a serve dispatcher may probe
# while clients read.  _misses caches fingerprints whose persisted-file
# lookup came back empty: get_profile sits on the exchange hot path
# (shuffle._choose reads it per sized exchange), so an unprobed mesh
# must cost one dict lookup, not one file read, per exchange.
_profiles: Dict[Tuple, "MeshProfile"] = {}
_misses: set = set()
_lock = threading.Lock()


@functools.lru_cache(maxsize=None)
def _fingerprint_of(mesh, axis: str) -> Tuple:
    # str()-ing every device is not free and the mesh is hashable —
    # memoize per (mesh, axis) so hot-path callers pay a cache hit
    try:
        devs = tuple(str(d) for d in mesh.devices.flat)
    except Exception:  # graftlint: ok[broad-except] — device repr
        devs = (str(mesh),)  # shape varies across jax versions
    return (axis, devs)


def mesh_fingerprint(ctx) -> Tuple:
    """Stable identity of one live mesh: axis name + the device set.
    The profile cache key — a rebuilt context over the same devices
    reuses the measured coefficients."""
    return _fingerprint_of(ctx.mesh, ctx.axis)


@dataclass(frozen=True)
class MeshProfile:
    """Fitted per-collective coefficients of one mesh.

    ``latency_s[c]``    α: fixed per-dispatch cost of collective ``c``
                        (the sync floor + launch overhead).
    ``bytes_per_s[c]``  β⁻¹: sustained per-device wire bandwidth.
    ``samples``         the raw ``(collective, wire_bytes, seconds)``
                        points the fit consumed (diagnostics; the
                        BENCH artifact can embed them).

    PER-EDGE coefficients (docs/tpu_perf_notes.md "Hierarchical
    collectives"): when the mesh has a non-trivial ``(slow, fast)``
    split, :func:`probe` additionally times each collective restricted
    to ONE axis of the 2-level mesh and fits those under the keys
    ``"<collective>@fast"`` / ``"<collective>@slow"`` — what turns
    ``cost.predicted_ms`` from a flat model into a per-edge one.
    ``axis_split`` records the split those keys were measured under.
    """

    fingerprint: Tuple
    latency_s: Dict[str, float]
    bytes_per_s: Dict[str, float]
    samples: Tuple[Tuple[str, int, float], ...]
    axis_split: Optional[Tuple[int, int]] = None

    def predicted_s(self, collective: str, wire_bytes: int,
                    rounds: int = 1) -> Optional[float]:
        """α·rounds + wire/β for one exchange; None for an unmeasured
        collective (a profile from a partial probe)."""
        lat = self.latency_s.get(collective)
        bw = self.bytes_per_s.get(collective)
        if lat is None or bw is None:
            return None
        return max(rounds, 1) * lat + wire_bytes / max(bw, 1.0)

    def describe(self) -> str:
        parts = []
        axis_keys = tuple(sorted(k for k in self.latency_s if "@" in k))
        for c in COLLECTIVES + TRANSFERS + axis_keys:
            if c in self.latency_s:
                parts.append(f"{c}: {self.latency_s[c] * 1e3:.3f} ms + "
                             f"{self.bytes_per_s[c] / 1e9:.3f} GB/s")
        return "; ".join(parts)


# ---------------------------------------------------------------------------
# probe kernels — one per collective, same shard_map idiom as the
# exchange lowerings (parallel/shuffle.py).  Each returns a per-shard
# [1] reduction of the moved payload so (a) XLA cannot dead-code the
# collective away and (b) the timed host read transfers P floats, not
# the payload.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _a2a_probe_fn(mesh, axis: str, nparts: int, m: int, spec_axes=None):
    spec = P(spec_axes if spec_axes is not None else axis)

    def kernel(x_blk):
        y = jax.lax.all_to_all(x_blk.reshape(nparts, m), axis, 0, 0,
                               tiled=True)
        return jnp.sum(y).reshape(1)

    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=spec, out_specs=spec),
                     name="meshprobe._a2a_probe_fn")


@functools.lru_cache(maxsize=None)
def _ppermute_probe_fn(mesh, axis: str, nparts: int, spec_axes=None):
    spec = P(spec_axes if spec_axes is not None else axis)
    perm = [(i, (i + 1) % nparts) for i in range(nparts)]

    def kernel(x_blk):
        y = jax.lax.ppermute(x_blk, axis, perm)
        return jnp.sum(y).reshape(1)

    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=spec, out_specs=spec),
                     name="meshprobe._ppermute_probe_fn")


@functools.lru_cache(maxsize=None)
def _allgather_probe_fn(mesh, axis: str, spec_axes=None):
    spec = P(spec_axes if spec_axes is not None else axis)

    def kernel(x_blk):
        y = jax.lax.all_gather(x_blk, axis, tiled=True)
        return jnp.sum(y).reshape(1)

    # check_vma=False: the gathered intermediate is replicated, which
    # shard_map cannot statically infer (same note as broadcast.py)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=spec, out_specs=spec,
                               check_vma=False),
                     name="meshprobe._allgather_probe_fn")


def _fit(points) -> Tuple[float, float]:
    """Least-squares α + bytes/β over (wire_bytes, seconds) points;
    degenerate fits (negative slope from noise, single point) degrade
    to a zero-latency / measured-mean-bandwidth model rather than
    returning nonsense coefficients."""
    xs = np.asarray([p[0] for p in points], dtype=np.float64)
    ts = np.asarray([p[1] for p in points], dtype=np.float64)
    if len(xs) >= 2 and float(np.ptp(xs)) > 0:
        slope, intercept = np.polyfit(xs, ts, 1)
    else:
        slope, intercept = 0.0, float(ts.min())
    if slope <= 0:
        # bandwidth too high to resolve at these sizes: latency-bound
        return max(float(ts.min()), 1e-9), 1e15
    return max(float(intercept), 0.0), 1.0 / float(slope)


def probe(ctx, sizes: Tuple[int, ...] = (1 << 12, 1 << 15, 1 << 18),
          reps: int = 2, force: bool = False) -> MeshProfile:
    """Run the microbench on ``ctx``'s mesh and cache the fitted
    profile (a cached fingerprint returns immediately unless ``force``).

    ``sizes`` are per-shard payload BYTES per collective dispatch
    (float32 payload, rounded down to whole elements; the all_to_all
    block is [P, size/P] per shard, matching the exchange kernel's
    shape).  Each (collective, size) point is dispatched once to
    compile, then ``reps`` times timed to hard completion
    (trace.hard_sync — the host-observed number including the
    completion round trip, exactly what an exchange dispatch pays); the minimum rep is the sample.
    """
    fp = mesh_fingerprint(ctx)
    if not force:
        hit = get_profile(ctx)
        if hit is not None:
            return hit
    mesh, axis, Pn = ctx.mesh, ctx.axis, ctx.get_world_size()
    samples = []
    rng = np.random.default_rng(7)
    with trace.span("meshprobe"):
        for size in sizes:
            # per-shard element count, padded so the [P, m] all_to_all
            # reshape divides evenly
            n = max((size // 4 // max(Pn, 1)) * max(Pn, 1), Pn)
            x = jax.device_put(
                rng.standard_normal(n * Pn).astype(np.float32),
                ctx.sharding())
            m = n // Pn
            wire_a2a = (Pn - 1) * m * 4
            wire_ring = n * 4
            wire_ag = (Pn - 1) * n * 4
            for coll, fn, wire in (
                    ("all_to_all",
                     _a2a_probe_fn(mesh, axis, Pn, m), wire_a2a),
                    ("ppermute",
                     _ppermute_probe_fn(mesh, axis, Pn), wire_ring),
                    ("all_gather",
                     _allgather_probe_fn(mesh, axis), wire_ag)):
                trace.hard_sync(fn(x))  # compile + warm outside the clock
                best = None
                for _ in range(max(reps, 1)):
                    t0 = time.perf_counter()
                    trace.hard_sync(fn(x))
                    dt = time.perf_counter() - t0
                    best = dt if best is None else min(best, dt)
                samples.append((coll, int(wire), float(best)))
            # the host-boundary legs of the staged-spill lowering: one
            # sharded device_put (h2d) and one device_get (d2h) of the
            # same payload, timed to hard completion like the
            # collectives — the spill pool's stage_in/stage_out pay
            # exactly these
            host = np.asarray(
                jax.device_get(x))  # graftlint: ok[implicit-host-sync]
            #                         — the transfer IS the measurement
            best_h = best_d = None
            for _ in range(max(reps, 1)):
                t0 = time.perf_counter()
                y = jax.device_put(host, ctx.sharding())
                trace.hard_sync(y)
                dt = time.perf_counter() - t0
                best_h = dt if best_h is None else min(best_h, dt)
                t0 = time.perf_counter()
                np.asarray(
                    jax.device_get(y))  # graftlint: ok[implicit-host-sync]
                dt = time.perf_counter() - t0
                best_d = dt if best_d is None else min(best_d, dt)
            samples.append(("h2d", int(host.nbytes), float(best_h)))
            samples.append(("d2h", int(host.nbytes), float(best_d)))
        # per-edge probes (docs/tpu_perf_notes.md "Hierarchical
        # collectives"): on a 2-level mesh, time each collective
        # RESTRICTED to one axis of the (slow, fast) view — the payload
        # stays sharded over both axes (the exchange kernels' layout),
        # only the collective's axis narrows.  The "@fast"/"@slow" fits
        # are what let cost.predicted_ms price a two-level sequence
        # edge by edge.
        split = None
        from .. import topology
        s_f = topology.axis_split(ctx)
        if s_f[0] > 1 and s_f[1] > 1 and s_f[0] * s_f[1] == Pn:
            split = (int(s_f[0]), int(s_f[1]))
            from ..context import MESH_FAST_AXIS, MESH_SLOW_AXIS
            mesh2 = ctx.mesh2d(split)
            axes2 = (MESH_SLOW_AXIS, MESH_FAST_AXIS)
            for size in sizes:
                for edge, ax_name, nA in (
                        ("fast", MESH_FAST_AXIS, split[1]),
                        ("slow", MESH_SLOW_AXIS, split[0])):
                    n = max((size // 4 // nA) * nA, nA)
                    x = jax.device_put(
                        rng.standard_normal(n * Pn).astype(np.float32),
                        ctx.sharding())
                    m = n // nA
                    for coll, fn, wire in (
                            ("all_to_all",
                             _a2a_probe_fn(mesh2, ax_name, nA, m, axes2),
                             (nA - 1) * m * 4),
                            ("ppermute",
                             _ppermute_probe_fn(mesh2, ax_name, nA,
                                                axes2),
                             n * 4),
                            ("all_gather",
                             _allgather_probe_fn(mesh2, ax_name, axes2),
                             (nA - 1) * n * 4)):
                        trace.hard_sync(fn(x))  # compile + warm
                        best = None
                        for _ in range(max(reps, 1)):
                            t0 = time.perf_counter()
                            trace.hard_sync(fn(x))
                            dt = time.perf_counter() - t0
                            best = dt if best is None else min(best, dt)
                        samples.append((f"{coll}@{edge}", int(wire),
                                        float(best)))
            trace.count("meshprobe.axis_probes")
    latency: Dict[str, float] = {}
    bw: Dict[str, float] = {}
    seen = []
    for c, _, _ in samples:
        if c not in seen:
            seen.append(c)
    for coll in seen:
        pts = [(w, t) for c, w, t in samples if c == coll]
        if pts:
            latency[coll], bw[coll] = _fit(pts)
    profile = MeshProfile(fp, latency, bw, tuple(samples),
                          axis_split=split)
    trace.count("meshprobe.probes")
    with _lock:
        _profiles[fp] = profile
        _misses.discard(fp)
    _persist(profile)
    return profile


def get_profile(ctx) -> Optional[MeshProfile]:
    """The cached profile for ``ctx``'s mesh, or None.  Never probes —
    reads the in-memory cache, falling back to the
    ``CYLON_MESHPROBE_PATH`` file when one is configured.  Misses are
    cached too (per process, until ``probe``/``clear_profiles``): this
    sits on the exchange hot path, so an unprobed mesh costs one set
    lookup per call, never repeated file reads."""
    fp = mesh_fingerprint(ctx)
    with _lock:
        hit = _profiles.get(fp)
        if hit is not None:
            return hit
        if fp in _misses:
            return None
    loaded = _load_persisted(fp)
    with _lock:
        if loaded is not None:
            _profiles.setdefault(fp, loaded)
        else:
            _misses.add(fp)
    return loaded


def put_profile(profile: MeshProfile) -> None:
    """Register a profile under its own fingerprint (and persist it
    when ``CYLON_MESHPROBE_PATH`` is set).  The injection seam for
    synthetic per-edge coefficients: CI's hierarchy smoke and the
    acceptance dryrun run on a CPU-simulated mesh whose physical slow
    edge does not exist, so they install a profile whose ``@slow``
    bandwidth reflects the topology being modelled and let the chooser
    rank for real (docs/observability.md)."""
    with _lock:
        _profiles[profile.fingerprint] = profile
        _misses.discard(profile.fingerprint)
    _persist(profile)


def clear_profiles() -> None:
    """Forget every cached profile AND cached miss (test isolation /
    re-reading a refreshed CYLON_MESHPROBE_PATH; the persisted file, if
    any, is untouched)."""
    with _lock:
        _profiles.clear()
        _misses.clear()


# ---------------------------------------------------------------------------
# optional persistence (CYLON_MESHPROBE_PATH): coefficients survive the
# process, so a serving restart on the same mesh skips the re-probe
# ---------------------------------------------------------------------------

def _fp_key(fp: Tuple) -> str:
    import hashlib
    return hashlib.sha1(repr(fp).encode()).hexdigest()[:16]


def _persist(profile: MeshProfile) -> None:
    path = os.environ.get("CYLON_MESHPROBE_PATH")
    if not path:
        return
    try:
        data = {}
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
        data[_fp_key(profile.fingerprint)] = {
            "fingerprint": list(profile.fingerprint[1]),
            "axis": profile.fingerprint[0],
            "latency_s": profile.latency_s,
            "bytes_per_s": profile.bytes_per_s,
            "samples": [list(s) for s in profile.samples],
            "axis_split": (list(profile.axis_split)
                           if profile.axis_split else None),
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, sort_keys=True)
        os.replace(tmp, path)
    except (OSError, ValueError):
        pass  # persistence is best-effort; the in-memory cache stands


def _load_persisted(fp: Tuple) -> Optional[MeshProfile]:
    path = os.environ.get("CYLON_MESHPROBE_PATH")
    if not path or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            data = json.load(f)
        rec = data.get(_fp_key(fp))
        if not isinstance(rec, dict):
            return None
        split = rec.get("axis_split")
        return MeshProfile(
            fp, dict(rec.get("latency_s", {})),
            dict(rec.get("bytes_per_s", {})),
            tuple(tuple(s) for s in rec.get("samples", ())),
            axis_split=tuple(split) if split else None)
    except (OSError, ValueError):
        return None
