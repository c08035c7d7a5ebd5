"""graftlint — AST linter for the hazard classes XLA cannot type-check.

The engine's performance contract rests on invariants invisible to the
Python type system: no implicit device→host transfer inside hot spans,
kernel factories keyed so jit/shard_map caches stay bounded, 64-bit
literals guarded by the x64 switch, and mesh-axis names flowing from the
mesh rather than string literals.  Each rule here encodes one of those
invariants as a static check (docs/static_analysis.md describes them
with examples):

  implicit-host-sync      ``.item()``; ``int()/float()/bool()`` /
                          ``np.asarray()/np.array()`` applied to a
                          device-valued expression; ``jax.device_get``
                          outside the allow-listed ingest/export modules.
  kernel-factory-unkeyed  a ``*_fn`` factory that builds jit/shard_map
                          programs without a cache decorator (every call
                          re-traces — the retrace-storm bug class), or
                          whose nested kernel closes over a name that is
                          not part of its cache key.
  program-unnamed         a bare ``jax.jit`` (call, decorator or
                          ``functools.partial(jax.jit, …)``) inside a
                          ``@kernel_factory`` factory or in the program
                          handed to a ``_cache_put`` program cache: its
                          program would run in the device trace under
                          the traced function's own name (``kernel``),
                          unreadable by factory — build it with
                          ``observe.compile.named_jit``.
  jit-in-loop             ``jax.jit``/``jax.pmap`` called inside a
                          ``for``/``while`` body.
  raw-float64-literal     ``jnp.{float64,int64,uint64,complex128}``
                          outside an ``enable_x64``-guarded branch
                          (breaks under the TPU-default x32 config
                          without the ``_jax_compat.enable_x64`` guard).
  shard-map-axis-literal  a string-literal axis name handed to
                          ``P()``/``PartitionSpec()`` or a ``jax.lax``
                          collective instead of the mesh's axis.
  broad-except            a bare ``except:`` / ``except Exception:`` /
                          ``except BaseException:`` handler that never
                          re-raises — it can swallow ``ReplayNeeded``
                          (breaking deferred-pipeline replay) or a typed
                          ``CylonError`` (docs/robustness.md).
  dist-op-unlowered       a new ``@plan_check.instrument`` ``dist_*``/
                          ``shuffle_*`` entry point in cylon_tpu/parallel/
                          with no lowering case in the plan executor's
                          LOWERING table (cylon_tpu/plan/executor.py) —
                          the op would silently fall off the optimized-
                          plan surface (docs/query_planner.md).
  counter-not-in-catalogue  a string-literal metric name bumped via
                          ``trace.count``/``count_max``/``gauge`` that
                          has no row in the observe catalogue
                          (cylon_tpu/observe/metrics.py METRICS) — the
                          catalogue is the docs' source of truth and
                          the ANALYZE compliance tests reject exactly
                          these at runtime; lint catches them at commit
                          time (docs/observability.md).  Dynamic names
                          (``cost.strategy_counter(...)``) are skipped.
  fault-point-not-in-catalogue  a string-literal point name consulted
                          via ``faults.check``/``faults.perturb`` that
                          has no row in the fault-point catalogue
                          (cylon_tpu/faults.py POINTS) — the catalogue
                          is the complete set of sanctioned failure
                          boundaries FaultPlan authors and
                          docs/robustness.md rely on; an uncatalogued
                          point would be injectable but invisible.
                          Dynamic names are skipped (mirrors
                          counter-not-in-catalogue).
  host-array-unpooled     a ``jax.device_get`` / ``np.asarray`` /
                          ``np.array`` materialization whose argument
                          is LEAF-SIZED (mentions a table leaf
                          attribute — ``.data``/``.validity``/
                          ``.pending_mask`` — or a ``leaves``-named
                          collection) outside the spill pool and the
                          sanctioned device↔host boundaries
                          (cylon_tpu/spill/pool.py
                          SANCTIONED_HOST_BOUNDARIES, parsed like the
                          metric/fault catalogues).  Column-sized host
                          copies made ad hoc bypass the host-tier
                          budget, the LRU and the staging fault
                          points — route them through
                          ``spill.pool.stage_out_arrays``
                          (docs/out_of_core.md).
  warn-once-key-literal   a ``glog.warn_once`` whose key is neither a
                          string literal nor a tuple opening with one —
                          a fully dynamic key makes every call unique,
                          defeating the once-per-signature rate limit
                          (the alert would spam) and leaving the alert
                          family ungreppable.  The sanctioned shapes:
                          ``warn_once("slo.p99-drift", …)`` and
                          ``warn_once(("shuffle.skew", hint_key), …)``
                          — the literal head names the family, dynamic
                          components scope the signature.
  shared-state-unguarded  a write (assignment, aug-assignment, ``del``,
                          or mutating container method) to a name the
                          module's ``GUARDED_STATE`` catalogue maps to
                          a lock, outside a ``with <that lock>`` block —
                          or an UNCATALOGUED module-level mutable
                          literal in a threaded module (one that
                          declares a catalogue or spawns threads).
                          Module top level, ``__init__``/``__new__``
                          bodies and ``*_locked`` functions (held-by-
                          contract) are exempt.  The catalogue format
                          and the runtime half (observe/locks.py
                          OrderedLock, the lock-order DAG) are in
                          docs/static_analysis.md "Concurrency
                          discipline".
  private-jax-api         a name from jax's ``core`` or ``_src``
                          modules (attribute or import) outside
                          ``cylon_tpu/_jax_compat.py`` — private names
                          move between JAX releases, so they live in ONE
                          shim and an upgrade breaks one file, not the
                          suite.
  blocking-call-under-lock  a call that can block indefinitely —
                          ``jax.block_until_ready`` / ``device_get`` /
                          ``serial_call`` / ``time.sleep`` /
                          ``.result()`` / thread ``.join()`` —
                          lexically inside a ``with <lock>`` body: the
                          exact shape of the XLA:CPU collective-
                          rendezvous deadlock (a thread blocks on
                          device work while holding the lock the
                          worker needs).  ``Condition.wait`` is exempt
                          (it releases the lock while waiting).

Findings carry ``file:line:col``; suppress a deliberate site with a
``# graftlint: ok[rule]`` (or bare ``# graftlint: ok``) comment on any
line the flagged expression spans.

CLI::

    python -m cylon_tpu.analysis.graftlint cylon_tpu bench.py

exits 0 when clean, 1 with findings, 2 on usage/parse errors.
"""
from __future__ import annotations

import ast
import os
import re
import symtable
import sys
import threading
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "RULES", "lint_source", "lint_paths", "main"]

RULES = (
    "implicit-host-sync",
    "kernel-factory-unkeyed",
    "program-unnamed",
    "jit-in-loop",
    "fault-point-not-in-catalogue",
    "raw-float64-literal",
    "shard-map-axis-literal",
    "broad-except",
    "dist-op-unlowered",
    "counter-not-in-catalogue",
    "warn-once-key-literal",
    "host-array-unpooled",
    "shared-state-unguarded",
    "blocking-call-under-lock",
    "private-jax-api",
)

# the one module allowed to touch private JAX names (private-jax-api)
_JAX_COMPAT = "cylon_tpu/_jax_compat.py"
_PRIVATE_JAX = tuple(f"jax.{m}" for m in ("core", "_src"))

# Modules whose job IS the device↔host boundary: ingest, export, the
# batched count protocol, the tracing sync, per-cell accessors.  A
# ``jax.device_get`` there is the sanctioned spelling; anywhere else it
# must be suppressed with a comment saying why.
DEVICE_GET_ALLOWED = (
    "cylon_tpu/trace.py",
    "cylon_tpu/table.py",
    "cylon_tpu/row.py",
    "cylon_tpu/parallel/dtable.py",
    "cylon_tpu/ops/compact.py",
    "cylon_tpu/io/",
    # the spill pool IS the sanctioned host-tier staging boundary
    # (docs/out_of_core.md); its batched stage_out device_get is the
    # route the host-array-unpooled rule points everyone else at
    "cylon_tpu/spill/pool.py",
    # observe/analyze.py is the EXPLAIN ANALYZE measurement boundary:
    # its row peeks are deliberate, explicit, per-operator host reads.
    # The REST of the observe package (registry, exporter, sampler,
    # stats store) is deliberately NOT allow-listed — the sampler's
    # zero-device-sync contract and the registry's host-only claim are
    # exactly what this lint guards
    "cylon_tpu/observe/analyze.py",
)

# Attribute names that hold device arrays throughout this codebase
# (DColumn/Column/DTable fields).  ``host_data``/``_counts_host`` are the
# host-side mirrors and intentionally absent.
_DEVICE_ATTRS = {"data", "counts", "validity", "pending_mask"}

# static metadata reads on a device array — no transfer involved
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "itemsize", "nbytes",
                 "is_fully_addressable", "sharding"}

# jnp dtypes that require the x64 switch to exist at all
_X64_DTYPES = {"float64", "int64", "uint64", "complex128"}

_AXIS_COLLECTIVES = {"all_gather", "psum", "pmax", "pmin", "all_to_all",
                     "axis_index", "psum_scatter", "ppermute", "pmean"}

_CACHE_DECORATORS = {"lru_cache", "cache", "kernel_factory"}

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*ok(?:\[([A-Za-z0-9_,\- ]+)\])?")


@dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}"


def _suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
    """line → None (all rules) or the set of rule names waived there."""
    out: Dict[int, Optional[Set[str]]] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        if m.group(1) is None:
            out[i] = None
        else:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.psum' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_deviceish(node: ast.AST) -> bool:
    """Syntactic heuristic: does this expression produce a DEVICE value?

    Tuned for precision over recall (a silent miss beats a noisy false
    positive): jnp/jax.lax call results, the device-array attributes of
    the table types, and method/index chains hanging off either.
    """
    if isinstance(node, ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return False  # static metadata of a device array, not data
        if node.attr in _DEVICE_ATTRS:
            return True
        return _is_deviceish(node.value)
    if isinstance(node, ast.Subscript):
        return _is_deviceish(node.value)
    if isinstance(node, ast.Call):
        target = _dotted(node.func)
        if target is not None:
            root = target.split(".", 1)[0]
            if root in ("jnp", "lax") or target.startswith("jax.lax.") \
                    or target.startswith("jax.numpy."):
                return True
        if isinstance(node.func, ast.Attribute):  # method chain: x.sum()
            return _is_deviceish(node.func.value)
    if isinstance(node, ast.BinOp):
        return _is_deviceish(node.left) or _is_deviceish(node.right)
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.findings: List[Finding] = []
        self.module_names: Set[str] = set()
        self.loop_depth = 0
        self._parents: Dict[ast.AST, ast.AST] = {}
        self._suppress = _suppressions(source)
        self._finding_lines: Dict[Tuple[int, int, str], Tuple[int, int]] = {}
        # concurrency-rule state (docs/static_analysis.md "Concurrency
        # discipline"): the module's GUARDED_STATE catalogue, the lock
        # names it references, and the lexical with/function context
        # maintained during traversal
        self.guarded: Optional[Dict[str, str]] = None
        self.lock_names: Set[str] = set()
        self._with_stack: List[str] = []
        self._func_stack: List[str] = []
        self._lc = None          # the lockcheck helper module (run())

    # -- plumbing -----------------------------------------------------------

    def run(self, tree: ast.Module) -> List[Finding]:
        from . import lockcheck as _lockcheck
        self._lc = _lockcheck
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node
        self.module_names = _module_bindings(tree)
        self.guarded = _lockcheck.guarded_state_from_tree(tree)
        self.lock_names = set(self.guarded.values()) if self.guarded \
            else set()
        if self.guarded is not None or _lockcheck.spawns_threads(tree):
            self._check_module_mutables(tree, _lockcheck)
        self.visit(tree)
        self._check_factories(tree)
        self._check_unnamed_programs(tree)
        self._check_unlowered(tree)
        return [f for f in self.findings if not self._suppressed(f)]

    def _suppressed(self, f: Finding) -> bool:
        node_lines = self._finding_lines.get((f.line, f.col, f.rule),
                                             (f.line, f.line))
        for line in range(node_lines[0], node_lines[1] + 1):
            rules = self._suppress.get(line, "missing")
            if rules is None or (rules != "missing" and f.rule in rules):
                return True
        return False

    def _emit(self, node: ast.AST, rule: str, message: str,
              def_line_only: bool = False) -> None:
        """``def_line_only`` narrows the suppression span to the node's
        first line — used for function-level findings, where the full
        span would let an unrelated suppression deep in the body waive
        the finding by accident."""
        f = Finding(self.path, node.lineno, node.col_offset, rule, message)
        end = node.lineno if def_line_only else (
            getattr(node, "end_lineno", node.lineno) or node.lineno)
        self._finding_lines[(f.line, f.col, rule)] = (node.lineno, end)
        self.findings.append(f)

    # -- traversal ----------------------------------------------------------

    def visit_For(self, node: ast.For) -> None:
        self._loop(node)

    def visit_AsyncFor(self, node: ast.AsyncFor) -> None:
        self._loop(node)

    def visit_While(self, node: ast.While) -> None:
        self._loop(node)

    def _loop(self, node) -> None:
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    def visit_Call(self, node: ast.Call) -> None:
        target = _dotted(node.func)
        self._check_host_sync(node, target)
        self._check_jit_in_loop(node, target)
        self._check_axis_literal(node, target)
        self._check_counter_catalogue(node, target)
        self._check_warn_once_key(node, target)
        self._check_fault_catalogue(node, target)
        self._check_host_unpooled(node, target)
        self._check_blocking_under_lock(node, target)
        self._check_mutating_call(node)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        self._check_x64_literal(node)
        if _dotted(node) in _PRIVATE_JAX:
            self._check_private_jax(node, _dotted(node))
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            self._check_private_jax(node, alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        self._check_private_jax(node, mod)
        if mod == "jax":
            for alias in node.names:
                self._check_private_jax(node, f"jax.{alias.name}")
        self.generic_visit(node)

    # -- private-jax-api -----------------------------------------------------

    def _check_private_jax(self, node: ast.AST, name: str) -> None:
        if not any(name == p or name.startswith(p + ".")
                   for p in _PRIVATE_JAX):
            return
        if self.path.replace(os.sep, "/").endswith(_JAX_COMPAT):
            return
        self._emit(node, "private-jax-api",
                   f"{name} is private JAX API: route it through "
                   "cylon_tpu/_jax_compat.py")

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        self._check_broad_except(node)
        self.generic_visit(node)

    def visit_With(self, node: ast.With) -> None:
        self._with(node)

    def visit_AsyncWith(self, node: ast.AsyncWith) -> None:
        self._with(node)

    def _with(self, node) -> None:
        leaves = []
        for item in node.items:
            d = _dotted(item.context_expr)
            if d:
                leaves.append(d.rsplit(".", 1)[-1])
        self._with_stack.extend(leaves)
        self.generic_visit(node)
        if leaves:
            del self._with_stack[-len(leaves):]

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._function(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._function(node)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._function(node, name="<lambda>")

    def _function(self, node, name: Optional[str] = None) -> None:
        # a function DEFINED inside a `with lock:` body runs later, not
        # under the lock — the lexical with-context must not leak into
        # its body (and vice versa for the function-name exemptions)
        saved = self._with_stack
        self._with_stack = []
        self._func_stack.append(name or node.name)
        self.generic_visit(node)
        self._func_stack.pop()
        self._with_stack = saved

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._check_guarded_write(t, t)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_guarded_write(node.target, node)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._check_guarded_write(node.target, node)
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            self._check_guarded_write(t, node)
        self.generic_visit(node)

    # -- shared-state-unguarded ----------------------------------------------

    @staticmethod
    def _write_leaf(target: ast.AST) -> Optional[str]:
        """The catalogued leaf name a write target touches:
        ``self._entries[k] = v`` and ``._entries.pop(k)`` both touch
        ``_entries``; ``self._n += 1`` touches ``_n``."""
        while isinstance(target, (ast.Subscript, ast.Starred)):
            target = target.value
        if isinstance(target, ast.Attribute):
            return target.attr
        if isinstance(target, ast.Name):
            return target.id
        return None

    def _exempt_context(self) -> bool:
        """Writes at module top level and in ``__init__``/``__new__``
        bodies initialize not-yet-shared objects; ``*_locked``
        functions hold the lock by contract (their callers own the
        ``with`` — the pool/stats naming convention)."""
        if not self._func_stack:
            return True
        fn = self._func_stack[-1]
        return fn in ("__init__", "__new__") or fn.endswith("_locked")

    def _check_guarded_write(self, target: ast.AST,
                             node: ast.AST) -> None:
        if not self.guarded:
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._check_guarded_write(elt, node)
            return
        leaf = self._write_leaf(target)
        if leaf is None or leaf not in self.guarded:
            return
        if self._exempt_context():
            return
        need = self.guarded[leaf]
        if need in self._with_stack:
            return
        self._emit(node, "shared-state-unguarded",
                   f"write to {leaf!r} outside `with {need}:` — the "
                   "GUARDED_STATE catalogue maps it to that lock "
                   "(hold the lock, move the write into a *_locked "
                   "helper, or fix the catalogue)")

    def _check_mutating_call(self, node: ast.Call) -> None:
        """``x.append(…)`` / ``.pop`` / ``.update`` … on a catalogued
        container is a write like any other."""
        if not self.guarded or not isinstance(node.func, ast.Attribute):
            return
        if node.func.attr not in self._lc.MUTATING_METHODS:
            return
        self._check_guarded_write(node.func.value, node)

    def _check_module_mutables(self, tree: ast.Module,
                               _lockcheck) -> None:
        """In a threaded module (declares GUARDED_STATE or spawns
        threads), every module-level mutable literal must be catalogued
        — an uncatalogued one is shared state the lint cannot protect.
        CONSTANT_CASE names are immutable-by-convention tables
        (METRICS, POINTS, LOWERING…) and exempt."""
        for node in tree.body:
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            for t in targets:
                if not isinstance(t, ast.Name):
                    continue
                name = t.id
                if (name == "GUARDED_STATE" or name.startswith("__")
                        or _lockcheck.is_constant_name(name)):
                    continue
                if self.guarded and (name in self.guarded
                                     or name in self.lock_names):
                    continue
                if not _lockcheck.is_mutable_literal(value):
                    continue
                self._emit(node, "shared-state-unguarded",
                           f"module-level mutable {name!r} in a "
                           "threaded module is not in the GUARDED_STATE "
                           "catalogue — map it to its guarding lock, or "
                           "rename to CONSTANT_CASE if it is an "
                           "immutable table")

    # -- blocking-call-under-lock --------------------------------------------

    def _innermost_lock(self) -> Optional[str]:
        for name in reversed(self._with_stack):
            if "lock" in name.lower() or name in self.lock_names:
                return name
        return None

    def _check_blocking_under_lock(self, node: ast.Call,
                                   target: Optional[str]) -> None:
        """A device sync / collective dispatch / thread rendezvous
        lexically inside a ``with <lock>`` body is the rendezvous-
        deadlock shape: the blocked work may need a thread that needs
        this lock.  ``Condition.wait`` is exempt — it RELEASES the lock
        while waiting, which is the sanctioned way to block under
        one."""
        lock = self._innermost_lock()
        if lock is None:
            return
        leaf = target.rsplit(".", 1)[-1] if target else None
        if target in self._lc.BLOCKING_CALLS or leaf == "serial_call":
            self._emit(node, "blocking-call-under-lock",
                       f"{target or leaf}() can block indefinitely "
                       f"while `with {lock}:` is held — move the "
                       "blocking work outside the lock (capture state "
                       "under it, block after release)")
            return
        if not isinstance(node.func, ast.Attribute):
            return
        if node.func.attr == "result":
            self._emit(node, "blocking-call-under-lock",
                       f".result() joins a future while `with {lock}:` "
                       "is held — the worker completing it may need "
                       "this lock; collect futures under the lock, "
                       "join them after release")
            return
        if node.func.attr == "join":
            # thread-join shapes only: t.join() / t.join(5.0) /
            # t.join(timeout=…).  str.join/os.path.join take non-
            # numeric positional args and are skipped.
            joinish = (not node.args
                       and not any(kw.arg != "timeout"
                                   for kw in node.keywords)) \
                or (len(node.args) == 1
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, (int, float))
                    and not isinstance(node.args[0].value, bool))
            if joinish and not (isinstance(node.func.value, ast.Constant)):
                self._emit(node, "blocking-call-under-lock",
                           f".join() rendezvouses with a thread while "
                           f"`with {lock}:` is held — if that thread "
                           "ever takes this lock, this is a deadlock; "
                           "join after release")

    # -- broad-except --------------------------------------------------------

    def _check_broad_except(self, node: ast.ExceptHandler) -> None:
        """A handler catching Exception/BaseException (or everything)
        that never re-raises swallows ``ReplayNeeded`` — the deferred
        pipeline's replay signal, which inherits Exception by design —
        and typed ``CylonError``s alike.  Handlers containing ANY
        ``raise`` are exempt (convert-and-reraise is the sanctioned
        shape); deliberate best-effort catches carry a suppression
        comment saying why."""
        broad_names = ("Exception", "BaseException",
                       "builtins.Exception", "builtins.BaseException")
        t = node.type
        if t is None:
            broad = True
        elif isinstance(t, ast.Tuple):
            broad = any(_dotted(e) in broad_names for e in t.elts)
        else:
            broad = _dotted(t) in broad_names
        if not broad:
            return
        if _has_handler_raise(node.body):
            return
        what = "bare `except:`" if t is None else \
            f"`except {_dotted(t) if not isinstance(t, ast.Tuple) else 'Exception'}:`"
        self._emit(node, "broad-except",
                   f"{what} with no re-raise can swallow ReplayNeeded / "
                   "CylonError — catch the specific exceptions, re-raise, "
                   "or suppress with a comment saying why the swallow is "
                   "safe", def_line_only=True)

    # -- implicit-host-sync --------------------------------------------------

    def _check_host_sync(self, node: ast.Call, target: Optional[str]) -> None:
        if (isinstance(node.func, ast.Attribute) and node.func.attr == "item"
                and not node.args and not node.keywords):
            self._emit(node, "implicit-host-sync",
                       ".item() blocks on a device→host transfer")
            return
        if target in ("int", "float", "bool") and len(node.args) == 1 \
                and _is_deviceish(node.args[0]):
            self._emit(node, "implicit-host-sync",
                       f"{target}() on a device value forces a host sync; "
                       "keep the value on device or read it via an explicit "
                       "batched jax.device_get")
            return
        if target in ("np.asarray", "np.array", "numpy.asarray",
                      "numpy.array") and node.args \
                and _is_deviceish(node.args[0]):
            self._emit(node, "implicit-host-sync",
                       f"{target}() on a device value is a hidden "
                       "device→host transfer")
            return
        if target in ("jax.device_get", "device_get"):
            norm = self.path.replace(os.sep, "/")
            if not any(a in norm for a in DEVICE_GET_ALLOWED):
                self._emit(node, "implicit-host-sync",
                           "jax.device_get outside the ingest/export "
                           "allow-list (route host reads through the "
                           "batched protocols in ops/compact.py or "
                           "DTable.counts_host)")

    # -- jit-in-loop ---------------------------------------------------------

    def _check_jit_in_loop(self, node: ast.Call,
                           target: Optional[str]) -> None:
        if self.loop_depth > 0 and target in ("jax.jit", "jit", "jax.pmap"):
            self._emit(node, "jit-in-loop",
                       f"{target}() inside a loop builds a fresh traced "
                       "program per iteration — hoist it (or a cached "
                       "factory) out of the loop")

    # -- raw-float64-literal -------------------------------------------------

    def _check_x64_literal(self, node: ast.Attribute) -> None:
        if node.attr not in _X64_DTYPES:
            return
        base = _dotted(node.value)
        if base not in ("jnp", "jax.numpy"):
            return
        if self._x64_guarded(node):
            return
        self._emit(node, "raw-float64-literal",
                   f"jnp.{node.attr} without an enable_x64 guard silently "
                   "narrows (or raises) under the TPU-default x32 config — "
                   "branch on jax.config.jax_enable_x64 or use "
                   "_jax_compat.enable_x64")

    def _x64_guarded(self, node: ast.AST) -> bool:
        cur = node
        while cur is not None:
            parent = self._parents.get(cur)
            if isinstance(parent, (ast.If, ast.IfExp)):
                try:
                    test_src = ast.get_source_segment(self.source,
                                                      parent.test) or ""
                except Exception:  # graftlint: ok[broad-except] — source-
                    test_src = ""  # segment recovery is cosmetic only
                if "enable_x64" in test_src or "x64" in test_src:
                    return True
            cur = parent
        return False

    # -- shard-map-axis-literal ----------------------------------------------

    def _check_axis_literal(self, node: ast.Call,
                            target: Optional[str]) -> None:
        if target in ("P", "PartitionSpec", "jax.sharding.PartitionSpec"):
            for arg in node.args:
                if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    self._emit(arg, "shard-map-axis-literal",
                               f"hardcoded axis name {arg.value!r} in "
                               f"{target}(…) — pass the mesh's axis "
                               "(ctx.axis / a factory parameter) instead")
            return
        leaf = target.rsplit(".", 1)[-1] if target else None
        if leaf in _AXIS_COLLECTIVES and (
                target.startswith("jax.lax.") or target.startswith("lax.")
                or target == leaf):
            candidates = list(node.args[1:]) + [
                kw.value for kw in node.keywords
                if kw.arg in ("axis_name", "axis")]
            for arg in candidates:
                if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    self._emit(arg, "shard-map-axis-literal",
                               f"hardcoded axis name {arg.value!r} in "
                               f"{leaf}(…) — pass the mesh's axis instead")

    # -- counter-not-in-catalogue --------------------------------------------

    def _check_counter_catalogue(self, node: ast.Call,
                                 target: Optional[str]) -> None:
        """Every string-literal metric name bumped through the trace
        API must have a row in the observe catalogue — the catalogue is
        what docs and the runtime compliance tests read; a name missing
        from it would tally invisibly.  Dynamic names (derived counter
        names like ``cost.strategy_counter(...)``) are skipped: their
        catalogue membership is proven by the runtime compliance sweep
        instead."""
        if target is None:
            return
        head, _, leaf = target.rpartition(".")
        if leaf not in _COUNTER_FNS:
            return
        norm = self.path.replace(os.sep, "/")
        if head not in ("trace", "_trace"):
            # bare count()/count_max()/gauge() are the trace module's
            # OWN internal spellings; anywhere else a bare name is some
            # unrelated local function, not a metric bump
            if head or not norm.endswith("cylon_tpu/trace.py"):
                return
        if not node.args:
            return
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            return  # dynamic name — runtime compliance covers it
        names = _metric_names(self.path)
        if names is None or arg.value in names:
            return
        self._emit(node, "counter-not-in-catalogue",
                   f"metric {arg.value!r} is not in the observe "
                   "catalogue (cylon_tpu/observe/metrics.py METRICS) — "
                   "add a row documenting its kind/unit/meaning, or "
                   "derive the name from a catalogued family")

    # -- host-array-unpooled -------------------------------------------------

    def _check_host_unpooled(self, node: ast.Call,
                             target: Optional[str]) -> None:
        """Leaf-sized device→host materializations must go through the
        spill pool (docs/out_of_core.md): a ``jax.device_get`` or
        ``np.asarray``/``np.array`` whose argument mentions a table
        leaf attribute (``.data``/``.validity``/``.pending_mask``) or
        a ``leaves`` collection, outside the sanctioned boundary list
        the pool itself publishes (``SANCTIONED_HOST_BOUNDARIES`` —
        mtime-cached AST parse like the metric and fault-point
        catalogues), bypasses the host budget, the LRU and the
        ``spill.stage_*`` fault points."""
        if target not in ("jax.device_get", "device_get", "np.asarray",
                          "np.array", "numpy.asarray", "numpy.array"):
            return
        if not node.args or not _is_leafish_host(node.args[0]):
            return
        allowed = _host_boundary_names(self.path)
        if allowed is None:
            return  # no pool module to check against (partial tree)
        norm = self.path.replace(os.sep, "/")
        if any(a in norm for a in allowed):
            return
        self._emit(node, "host-array-unpooled",
                   f"{target}() materializes leaf-sized data outside "
                   "the spill pool / sanctioned boundaries — route it "
                   "through spill.pool.stage_out_arrays so the host "
                   "budget, LRU and staging fault points apply "
                   "(docs/out_of_core.md)")

    # -- warn-once-key-literal -----------------------------------------------

    def _check_warn_once_key(self, node: ast.Call,
                             target: Optional[str]) -> None:
        """``glog.warn_once`` keys must open with a string literal: the
        literal head is what makes the once-per-signature rate limit a
        rate limit (a fully dynamic key is unique per call → the alert
        spams) and what makes the alert family greppable from a log
        line back to its source (docs/observability.md "SLO rules")."""
        if target is None or not node.args:
            return
        head, _, leaf = target.rpartition(".")
        if leaf != "warn_once":
            return
        if head not in ("glog", "logging") and not (
                head == "" and self.path.replace(os.sep, "/")
                .endswith("cylon_tpu/logging.py")):
            return
        key = node.args[0]
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            return
        if (isinstance(key, ast.Tuple) and key.elts
                and isinstance(key.elts[0], ast.Constant)
                and isinstance(key.elts[0].value, str)):
            return
        self._emit(key, "warn-once-key-literal",
                   "warn_once key must be a string literal or a tuple "
                   "opening with one — a fully dynamic key defeats the "
                   "once-per-signature rate limit and makes the alert "
                   "family ungreppable")

    # -- fault-point-not-in-catalogue ----------------------------------------

    def _check_fault_catalogue(self, node: ast.Call,
                               target: Optional[str]) -> None:
        """Every string-literal point name consulted through
        ``faults.check``/``faults.perturb`` must have a row in the
        fault-point catalogue (cylon_tpu/faults.py POINTS) — the
        catalogue is what docs/robustness.md and the chaos suite treat
        as the complete set of sanctioned failure boundaries; an
        uncatalogued point would be injectable but undocumented and
        invisible to FaultPlan authors.  Mirrors
        counter-not-in-catalogue; dynamic names are skipped."""
        if target is None or not node.args:
            return
        head, _, leaf = target.rpartition(".")
        if leaf not in ("check", "perturb"):
            return
        norm = self.path.replace(os.sep, "/")
        if head not in ("faults", "_faults"):
            # bare check()/perturb() are the faults module's own
            # internal spellings; anywhere else a bare name is some
            # unrelated local function, not a fault-point consult
            if head or not norm.endswith("cylon_tpu/faults.py"):
                return
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            return  # dynamic point name — runtime coverage owns it
        names = _fault_point_names(self.path)
        if names is None or arg.value in names:
            return
        self._emit(node, "fault-point-not-in-catalogue",
                   f"fault point {arg.value!r} is not in the faults "
                   "catalogue (cylon_tpu/faults.py POINTS) — add a row "
                   "documenting what a fault there simulates")

    # -- dist-op-unlowered ---------------------------------------------------

    def _check_unlowered(self, tree: ast.Module) -> None:
        """Every instrumented ``dist_*``/``shuffle_*`` entry point in the
        parallel layer must have a case in the plan executor's LOWERING
        table, or the optimizer surface silently loses it as the op
        surface grows (docs/query_planner.md)."""
        keys = _lowering_keys(self.path)
        if keys is None:
            return  # not a parallel-layer file, or no executor to check
        for node in tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            if not _DIST_OP_RE.match(node.name):
                continue
            deco_exprs = [d.func if isinstance(d, ast.Call) else d
                          for d in node.decorator_list]
            instrumented = any(_dotted(d) in _INSTRUMENT_DECOS
                               for d in deco_exprs)
            if not instrumented:
                continue
            if node.name not in keys:
                self._emit(node, "dist-op-unlowered",
                           f"distributed op {node.name!r} has no lowering "
                           "case in cylon_tpu/plan/executor.py LOWERING — "
                           "add one (plus a CAPTURED_OPS spec in "
                           "plan/ir.py) so optimized plans keep covering "
                           "the whole op surface", def_line_only=True)

    # -- kernel-factory-unkeyed ----------------------------------------------

    def _check_factories(self, tree: ast.Module) -> None:
        blocks = {}
        try:
            table = symtable.symtable(self.source, self.path, "exec")
            _index_symtable(table, blocks)
        except Exception:  # graftlint: ok[broad-except]
            # symtable alone is best-effort: without it the closure-
            # capture arm degrades (blocks stay empty), but the uncached-
            # factory arm below must keep firing — a blanket except here
            # would silently turn the whole rule off
            pass
        for node in ast.walk(tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            if not node.name.endswith("_fn"):
                continue
            builds = [n for n in ast.walk(node) if isinstance(n, ast.Call)
                      and _dotted(n.func) in ("jax.jit", "jit", "shard_map",
                                              "jax.shard_map", "named_jit")]
            if not builds:
                continue
            deco_exprs = [d.func if isinstance(d, ast.Call) else d
                          for d in node.decorator_list]
            cached = any(
                (_dotted(d) or "").rsplit(".", 1)[-1] in _CACHE_DECORATORS
                for d in deco_exprs)
            if not cached:
                self._emit(node, "kernel-factory-unkeyed",
                           f"kernel factory {node.name!r} builds a "
                           "jit/shard_map program but has no cache "
                           "decorator — every call re-traces (decorate "
                           "with functools.lru_cache keyed on the static "
                           "arguments)", def_line_only=True)
                continue
            params = {a.arg for a in (node.args.posonlyargs + node.args.args
                                      + node.args.kwonlyargs)}
            if node.args.vararg:
                params.add(node.args.vararg.arg)
            if node.args.kwarg:
                params.add(node.args.kwarg.arg)
            fblock = blocks.get((node.name, node.lineno))
            if fblock is None:
                continue
            flocals = set(fblock.get_locals()) | params
            for child, enclosing in _nested_function_blocks(fblock, flocals):
                for free in child.get_frees():
                    if free in enclosing or free in self.module_names:
                        continue
                    self._emit(node, "kernel-factory-unkeyed",
                               f"kernel {child.get_name()!r} inside "
                               f"{node.name!r} closes over {free!r}, which "
                               "is not part of the factory's cache key — "
                               "thread it through the (hashable) factory "
                               "arguments", def_line_only=True)


    # -- program-unnamed -----------------------------------------------------

    def _check_unnamed_programs(self, tree: ast.Module) -> None:
        """Every program a kernel factory or the ``_cache_put`` program
        cache builds goes through ``named_jit``, so the device trace
        names it by factory (observe/compile.py)."""
        scopes = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and any((_dotted(d.func if isinstance(d, ast.Call)
                                     else d) or "").rsplit(".", 1)[-1]
                            == "kernel_factory"
                            for d in node.decorator_list):
                scopes.append((node, f"kernel factory {node.name!r}"))
            elif isinstance(node, ast.Call) and (
                    _dotted(node.func) or "").rsplit(".", 1)[-1] \
                    == "_cache_put":
                scopes.append((node, "the _cache_put program cache"))
        seen = set()
        for scope, where in scopes:
            for n in ast.walk(scope):
                bare = _bare_jit(n)
                if bare is None or id(bare) in seen:
                    continue
                seen.add(id(bare))
                self._emit(bare, "program-unnamed",
                           f"bare jax.jit in {where}: the program runs "
                           "in the device trace as 'jit_<traced "
                           "function>' — build it with "
                           "observe.compile.named_jit so the trace names "
                           "it by factory")


def _bare_jit(node: ast.AST) -> Optional[ast.AST]:
    """The ``jax.jit`` reference that ``node`` builds a program with —
    a ``jax.jit(...)`` call, a ``functools.partial(jax.jit, ...)``, or a
    bare ``@jax.jit`` decorator — else None."""
    jits = ("jax.jit", "jit")
    if isinstance(node, ast.Call):
        target = _dotted(node.func)
        if target in jits:
            return node
        if (target or "").rsplit(".", 1)[-1] == "partial" and node.args \
                and _dotted(node.args[0]) in jits:
            return node
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        for d in node.decorator_list:
            if _dotted(d) in jits:
                return d
    return None


_INSTRUMENT_DECOS = ("plan_check.instrument", "instrument")
_DIST_OP_RE = re.compile(r"^(dist|shuffle)_[a-z0-9_]+$")

_COUNTER_FNS = {"count", "count_max", "gauge", "hist"}

# One shared mtime-cached "parse a catalogue literal out of a sibling
# file" helper behind the three catalogue-backed rules.  Cache entries
# are keyed by the catalogue file's path + mtime, so an edit during a
# long-lived process invalidates the parse.  Every arm is best-effort:
# an unlocatable/unparseable catalogue returns None and the rule stays
# silent (like the symtable arm of kernel-factory-unkeyed).
#
# The whole check-then-parse-then-store sequence holds _catalogue_lock:
# concurrent linters (pytest workers sharing the process, an IDE
# integration) used to race the plain-dict check-then-act and parse the
# same catalogue twice — benign for the result but exactly the pattern
# the shared-state-unguarded rule exists to forbid.  A plain
# threading.Lock (not OrderedLock) on purpose: graftlint must stay
# stdlib-importable (see analysis/__init__), and the lock is leaf-level
# by construction.
_catalogue_lock = threading.Lock()
_catalogue_cache: Dict[Tuple[str, str],
                       Tuple[float, Optional[frozenset]]] = {}

GUARDED_STATE = {"_catalogue_cache": "_catalogue_lock"}


def _sibling_names(linted_path: str, anchor: str, rel_file: str,
                   var_name: str, extract) -> Optional[frozenset]:
    """String names extracted from the ``var_name = <literal>``
    assignment in ``rel_file`` (located relative to ``linted_path`` via
    its last ``anchor`` component); ``extract(value_node)`` maps the
    assigned AST literal to a set of names or None."""
    norm = linted_path.replace(os.sep, "/")
    idx = norm.rfind(anchor)
    if idx < 0:
        return None
    cat_path = norm[:idx] + rel_file
    try:
        mtime = os.path.getmtime(cat_path)
    except OSError:
        return None
    with _catalogue_lock:
        hit = _catalogue_cache.get((cat_path, var_name))
        if hit is not None and hit[0] == mtime:
            return hit[1]
        names: Optional[frozenset] = None
        try:
            with open(cat_path, "r", encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=cat_path)
            for node in tree.body:
                if isinstance(node, ast.AnnAssign):
                    targets = [node.target]
                    value = node.value
                elif isinstance(node, ast.Assign):
                    targets = node.targets
                    value = node.value
                else:
                    continue
                if not any(isinstance(t, ast.Name) and t.id == var_name
                           for t in targets):
                    continue
                names = extract(value)
        except (OSError, SyntaxError):
            names = None
        _catalogue_cache[(cat_path, var_name)] = (mtime, names)
    return names


def _dict_str_keys(value: ast.AST) -> Optional[frozenset]:
    if not isinstance(value, ast.Dict):
        return None
    return frozenset(k.value for k in value.keys
                     if isinstance(k, ast.Constant)
                     and isinstance(k.value, str))


def _metric_names(linted_path: str) -> Optional[frozenset]:
    """Metric names of the observe catalogue, parsed from the
    ``METRICS ... = _specs((name, kind, unit, doc), ...)`` literal in
    cylon_tpu/observe/metrics.py (located relative to the linted
    file)."""
    def rows(value: ast.AST) -> Optional[frozenset]:
        if not isinstance(value, ast.Call):
            return None
        return frozenset(
            row.elts[0].value for row in value.args
            if isinstance(row, ast.Tuple) and row.elts
            and isinstance(row.elts[0], ast.Constant)
            and isinstance(row.elts[0].value, str))
    return _sibling_names(linted_path, "cylon_tpu/",
                          "cylon_tpu/observe/metrics.py", "METRICS",
                          rows)


_LEAF_ATTRS = {"data", "validity", "pending_mask"}


def _is_leafish_host(node: ast.AST) -> bool:
    """Does this expression plausibly reference table-leaf-sized
    arrays?  Tuned for precision like ``_is_deviceish``: leaf
    attributes of the table types, or a ``leaves``-named collection."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _LEAF_ATTRS:
            return True
        if isinstance(sub, ast.Name) and ("leaves" in sub.id
                                          or sub.id == "leaf"):
            return True
    return False


def _host_boundary_names(linted_path: str) -> Optional[frozenset]:
    """The sanctioned device↔host boundary paths, parsed from the
    ``SANCTIONED_HOST_BOUNDARIES = (...)`` literal in
    cylon_tpu/spill/pool.py (located relative to the linted file —
    the same mtime-cached idiom as the metric catalogue)."""
    def rows(value: ast.AST) -> Optional[frozenset]:
        if not isinstance(value, (ast.Tuple, ast.List)):
            return None
        return frozenset(e.value for e in value.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str))
    return _sibling_names(linted_path, "cylon_tpu/",
                          "cylon_tpu/spill/pool.py",
                          "SANCTIONED_HOST_BOUNDARIES", rows)


def _fault_point_names(linted_path: str) -> Optional[frozenset]:
    """Fault-point names of the catalogue, parsed from the
    ``POINTS: Dict[str, str] = {...}`` literal in cylon_tpu/faults.py
    (located relative to the linted file)."""
    return _sibling_names(linted_path, "cylon_tpu/",
                          "cylon_tpu/faults.py", "POINTS",
                          _dict_str_keys)


def _lowering_keys(linted_path: str) -> Optional[frozenset]:
    """String keys of the plan executor's LOWERING dict, located
    relative to the linted file (…/cylon_tpu/parallel/X.py →
    …/cylon_tpu/plan/executor.py) — only parallel-layer files are
    checked, so the anchor is the parallel/ component."""
    return _sibling_names(linted_path, "cylon_tpu/parallel/",
                          "cylon_tpu/plan/executor.py", "LOWERING",
                          _dict_str_keys)


def _has_handler_raise(body) -> bool:
    """A ``raise`` that can actually execute as part of the handler:
    raises inside a nested function/lambda/class defined in the handler
    body do NOT run when the handler does, so they must not exempt it."""
    stack = list(body)
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Raise):
            return True
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(n))
    return False


def _nested_function_blocks(block, enclosing: Set[str]) -> Iterable:
    """(function block, names bound in any enclosing scope) pairs — a
    genexpr inside the kernel legitimately closes over kernel locals."""
    for child in block.get_children():
        if child.get_type() == "function":
            yield child, enclosing
            yield from _nested_function_blocks(
                child, enclosing | set(child.get_locals()))


def _index_symtable(table, out: Dict[Tuple[str, int], object]) -> None:
    for child in table.get_children():
        if child.get_type() == "function":
            out[(child.get_name(), child.get_lineno())] = child
        _index_symtable(child, out)


def _module_bindings(tree: ast.Module) -> Set[str]:
    names: Set[str] = set()
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                names.add((a.asname or a.name).split(".")[0])
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        names.add(n.id)
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                            ast.Name):
            names.add(node.target.id)
    return names


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def lint_source(source: str, path: str = "<string>") -> List[Finding]:
    """Lint one module's source; returns unsuppressed findings."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, e.offset or 0,
                        "parse-error", str(e))]
    return _Linter(path, source).run(tree)


def _iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)
        else:
            yield p


def lint_paths(paths: Sequence[str]) -> List[Finding]:
    findings: List[Finding] = []
    for path in _iter_py_files(paths):
        with open(path, "r", encoding="utf-8") as fh:
            findings.extend(lint_source(fh.read(), path))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--list-rules" in argv:
        for r in RULES:
            print(r)
        return 0
    paths = [a for a in argv if not a.startswith("-")]
    if not paths:
        print("usage: python -m cylon_tpu.analysis.graftlint "
              "[--list-rules] PATH [PATH ...]", file=sys.stderr)
        return 2
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"graftlint: no such path: {', '.join(missing)}",
              file=sys.stderr)
        return 2
    findings = lint_paths(paths)
    for f in findings:
        print(f)
    if any(f.rule == "parse-error" for f in findings):
        # a syntactically broken tree is a tooling failure, not lint
        # findings — the documented exit-code contract separates them
        print("graftlint: parse error", file=sys.stderr)
        return 2
    if findings:
        print(f"graftlint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
