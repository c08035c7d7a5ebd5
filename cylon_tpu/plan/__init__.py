"""Logical query planner: lazy plan IR, rewrite rules, compiled-plan cache.

The layer between the user-facing plan functions and the distributed
operators (docs/query_planner.md):

  * ``ir``        — the lazy IR: while a capture is active, every public
                    dist-op call builds a typed :class:`ir.Node` instead
                    of executing (the ``plan_check.instrument`` hook —
                    EXPLAIN, EXPLAIN ANALYZE and the optimizer share one
                    tracer);
  * ``rules``     — the rewrite engine: projection pruning, filter
                    pushdown, plan-time join strategy, common-subplan
                    elimination;
  * ``executor``  — lowering back onto the eager ops + the compiled-plan
                    cache keyed by (plan structure, schemas, ingest
                    counts, world size, config fingerprint).

User surfaces: ``ctx.optimize(plan_fn, tables)`` and
``DTable.explain(plan, tables=…, optimize=True)``.  ``CYLON_OPTIMIZER=0``
(or ``config.set_optimizer_enabled(False)``) is the escape hatch — plans
then run eagerly, byte-for-byte the pre-planner behavior.
"""
from __future__ import annotations

from . import executor, ir, rules  # noqa: F401  (re-exported submodules)
from .executor import clear_plan_cache, plan_cache_len
from .ir import Builder, LogicalTable

__all__ = ["optimize", "run", "Builder", "LogicalTable",
           "clear_plan_cache", "plan_cache_len", "ir", "rules",
           "executor"]


def run(ctx, op, tables=None):
    """Capture, optimize and execute ``op`` unconditionally (no enable
    check) — the core ``ctx.optimize`` delegates to, and the callable
    the explain surfaces wrap.  ``op`` receives ``tables`` (a dict of
    DTables, a single DTable, or None) with every table replaced by a
    lazy :class:`ir.LogicalTable`; the return value is materialized back
    to concrete tables before returning.

    The context resolves through the elastic-topology registry
    (cylon_tpu/topology.py): after a mid-query device loss re-meshed
    the process onto a survivor mesh, every subsequent plan anchors on
    it automatically — degraded throughput, same answers
    (docs/robustness.md "Elasticity")."""
    from .. import topology
    if tables is not None:
        # tables a previous victim's rung never scanned are still on
        # the old mesh — migrate them here, before pricing reads their
        # layout, instead of paying another device on first touch
        # (whole-mesh tables make this a dict lookup per table)
        from ..parallel.remesh import ensure_current
        ensure_current(tables)
    b = Builder(topology.effective(ctx))
    wrapped = b.wrap_tables(tables) if tables is not None else None
    with ir.capture(b):
        out = op(wrapped) if tables is not None else op()
        return b.finish(out)


def optimize(ctx, op, tables=None):
    """Run ``op(tables)`` through the logical planner: capture the plan
    lazily, rewrite it (plan/rules.py), execute the optimized DAG via
    the compiled-plan cache (plan/executor.py).  With the optimizer
    disabled (``CYLON_OPTIMIZER=0`` / ``config.set_optimizer_enabled``)
    the plan runs eagerly instead — the A/B lever bench uses."""
    from .. import trace
    from ..config import optimizer_enabled
    with trace.planner():
        if not optimizer_enabled():
            return op(tables) if tables is not None else op()
        return run(ctx, op, tables)
