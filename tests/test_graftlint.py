"""graftlint: per-rule fixtures (positive / suppressed / clean), the CLI
exit contract, and the tier-1 self-lint gate — ``cylon_tpu``, ``bench.py``
and ``chip_smoke.py`` must stay at zero unsuppressed findings, so a new hidden host sync fails
the build right here."""
import os
import subprocess
import sys

import pytest

from cylon_tpu.analysis import graftlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(src, path="fixture.py"):
    return sorted({f.rule for f in graftlint.lint_source(src, path)})


# ---------------------------------------------------------------------------
# rule fixtures: each rule fires on its positive snippet, stays quiet when
# suppressed, and stays quiet on the clean spelling
# ---------------------------------------------------------------------------

def test_implicit_host_sync_item():
    assert _rules("x = v.item()\n") == ["implicit-host-sync"]
    assert _rules("x = v.item()  # graftlint: ok[implicit-host-sync]\n") == []


def test_implicit_host_sync_scalar_casts():
    pos = "import jax.numpy as jnp\nn = int(jnp.sum(dt.counts))\n"
    assert _rules(pos) == ["implicit-host-sync"]
    # host values (numpy results of an explicit batched read) are fine
    clean = "n = int(per_shard.max(initial=0))\n"
    assert _rules(clean) == []
    # static metadata of a device array is not data
    assert _rules("n = int(col.data.shape[0])\n") == []


def test_implicit_host_sync_np_asarray():
    pos = "import numpy as np\nh = np.asarray(c.data)\n"
    assert _rules(pos) == ["implicit-host-sync"]
    assert _rules("import numpy as np\nh = np.asarray(host_rows)\n") == []


def test_implicit_host_sync_device_get_allowlist():
    src = "import jax\nv = jax.device_get(dt.counts)\n"
    assert _rules(src, "cylon_tpu/parallel/dist_ops.py") \
        == ["implicit-host-sync"]
    # the ingest/export modules are the sanctioned boundary
    assert _rules(src, "cylon_tpu/parallel/dtable.py") == []
    assert _rules(src, "cylon_tpu/ops/compact.py") == []


def test_kernel_factory_unkeyed():
    pos = ("import jax\n"
           "def _probe_fn(mesh, axis, cap):\n"
           "    def kernel(x):\n"
           "        return x\n"
           "    return jax.jit(kernel)\n")
    assert _rules(pos) == ["kernel-factory-unkeyed"]
    clean = ("import functools, jax\n"
             "@functools.lru_cache(maxsize=None)\n"
             "def _probe_fn(mesh, axis, cap):\n"
             "    def kernel(x):\n"
             "        return x + cap\n"
             "    return jax.jit(kernel)\n")
    assert _rules(clean) == []
    sup = pos.replace("def _probe_fn(mesh, axis, cap):",
                      "def _probe_fn(mesh, axis, cap):"
                      "  # graftlint: ok[kernel-factory-unkeyed]")
    assert _rules(sup) == []


@pytest.mark.parametrize("body", [
    "    def kernel(x):\n        return x + n\n    return jax.jit(kernel)\n",
    "    @jax.jit\n    def f(x):\n        return x + n\n    return f\n",
    "    return functools.partial(jax.jit, static_argnums=0)(\n"
    "        lambda k, x: x + n)\n",
])
def test_program_unnamed_in_kernel_factory(body):
    head = ("import functools, jax\n"
            "from cylon_tpu.observe.compile import kernel_factory\n"
            "@kernel_factory\n"
            "def _probe_fn(n):\n")
    assert _rules(head + body) == ["program-unnamed"]
    lines = (head + body).splitlines()
    flagged = graftlint.lint_source(head + body, "fixture.py")[0].line
    lines[flagged - 1] += "  # graftlint: ok[program-unnamed]"
    assert _rules("\n".join(lines) + "\n") == []
    clean = ("from cylon_tpu.observe.compile import kernel_factory, "
             "named_jit\n"
             "@kernel_factory\n"
             "def _probe_fn(n):\n"
             "    def kernel(x):\n"
             "        return x + n\n"
             "    return named_jit(kernel)\n")
    assert _rules(clean) == []


def test_program_unnamed_in_program_cache():
    pos = ("import jax\n"
           "def select(key, kernel):\n"
           "    return _cache_put(key, jax.jit(kernel))\n")
    assert _rules(pos) == ["program-unnamed"]
    clean = ("from cylon_tpu.observe.compile import named_jit\n"
             "def select(key, kernel):\n"
             "    return _cache_put(key, named_jit(kernel, "
             "name='m.select'))\n")
    assert _rules(clean) == []
    # a plain lru_cache factory and a bare jit elsewhere are not this
    # rule's business
    other = ("import functools, jax\n"
             "@functools.lru_cache(maxsize=None)\n"
             "def _probe_fn(n):\n"
             "    return jax.jit(lambda x: x + n)\n"
             "f = jax.jit(lambda x: x)\n")
    assert _rules(other) == []


def test_jit_in_loop():
    pos = ("import jax\n"
           "for i in range(3):\n"
           "    f = jax.jit(lambda x: x + i)\n")
    assert _rules(pos) == ["jit-in-loop"]
    clean = ("import jax\n"
             "f = jax.jit(lambda x: x + 1)\n"
             "for i in range(3):\n"
             "    y = f(i)\n")
    assert _rules(clean) == []


def test_raw_float64_literal():
    assert _rules("import jax.numpy as jnp\nd = jnp.float64\n") \
        == ["raw-float64-literal"]
    # the codebase idiom: branch on the x64 switch
    guarded = ("import jax, jax.numpy as jnp\n"
               "d = jnp.float64 if jax.config.jax_enable_x64 "
               "else jnp.float32\n")
    assert _rules(guarded) == []
    sup = ("import jax.numpy as jnp\n"
           "d = jnp.float64  # graftlint: ok[raw-float64-literal]\n")
    assert _rules(sup) == []


def test_shard_map_axis_literal():
    pos = ("from jax.sharding import PartitionSpec as P\n"
           "spec = P('p')\n")
    assert _rules(pos) == ["shard-map-axis-literal"]
    pos2 = "import jax\ng = jax.lax.all_gather(x, 'p')\n"
    assert _rules(pos2) == ["shard-map-axis-literal"]
    clean = ("from jax.sharding import PartitionSpec as P\n"
             "def f(axis):\n"
             "    return P(axis)\n")
    assert _rules(clean) == []


def test_broad_except_flags_silent_swallow():
    pos = "try:\n    x()\nexcept Exception:\n    pass\n"
    assert _rules(pos) == ["broad-except"]
    bare = "try:\n    x()\nexcept:\n    pass\n"
    assert _rules(bare) == ["broad-except"]
    base = "try:\n    x()\nexcept BaseException:\n    out = None\n"
    assert _rules(base) == ["broad-except"]
    tup = "try:\n    x()\nexcept (ValueError, Exception):\n    pass\n"
    assert _rules(tup) == ["broad-except"]


def test_broad_except_reraise_and_specific_are_clean():
    # convert-and-reraise is the sanctioned broad shape
    reraise = ("try:\n    x()\nexcept Exception as e:\n"
               "    raise CylonError(str(e)) from e\n")
    assert _rules(reraise) == []
    # catching a SPECIFIC exception never swallows ReplayNeeded
    spec = "try:\n    x()\nexcept ValueError:\n    pass\n"
    assert _rules(spec) == []
    # a conditional re-raise inside the handler also counts
    cond = ("try:\n    x()\nexcept Exception as e:\n"
            "    if bad(e):\n        raise\n    log(e)\n")
    assert _rules(cond) == []
    # ...but a raise inside a NESTED function never runs as part of the
    # handler and must not exempt it
    nested = ("try:\n    x()\nexcept Exception:\n"
              "    def _cleanup():\n        raise RuntimeError('x')\n"
              "    pass\n")
    assert _rules(nested) == ["broad-except"]


def test_broad_except_suppression_on_the_except_line():
    src = ("try:\n    x()\n"
           "except Exception:  # graftlint: ok[broad-except]\n"
           "    pass\n")
    assert _rules(src) == []
    # a suppression buried in the handler BODY must not waive it (the
    # finding is narrowed to the except line, like function findings)
    buried = ("try:\n    x()\nexcept Exception:\n"
              "    y = 1  # graftlint: ok[broad-except]\n")
    assert _rules(buried) == ["broad-except"]


def test_bare_suppression_waives_all_rules():
    assert _rules("x = v.item()  # graftlint: ok\n") == []


def test_multiline_expression_suppression():
    src = ("import numpy as np\n"
           "h = np.asarray(\n"
           "    c.data)  # graftlint: ok[implicit-host-sync]\n")
    assert _rules(src) == []


# ---------------------------------------------------------------------------
# CLI contract + tier-1 self-lint gate
# ---------------------------------------------------------------------------

def test_dist_op_unlowered_fires_on_uncovered_entry_point():
    """An instrumented ``dist_*`` entry point in the parallel layer with
    no case in the plan executor's LOWERING table falls off the
    optimized-plan surface — the rule keeps the IR total as the op
    surface grows (docs/query_planner.md)."""
    path = os.path.join(REPO, "cylon_tpu", "parallel", "zz_fixture.py")
    pos = ("from ..analysis import plan_check\n"
           "@plan_check.instrument\n"
           "def dist_frobnicate(dt):\n"
           "    return dt\n")
    assert _rules(pos, path) == ["dist-op-unlowered"]
    sup = pos.replace(
        "def dist_frobnicate(dt):",
        "def dist_frobnicate(dt):  # graftlint: ok[dist-op-unlowered]")
    assert _rules(sup, path) == []
    # a lowered op and a plain (uninstrumented) helper both stay quiet
    covered = pos.replace("dist_frobnicate", "dist_join")
    assert _rules(covered, path) == []
    helper = "def dist_helper(dt):\n    return dt\n"
    assert _rules(helper, path) == []
    # outside the parallel layer the rule does not apply
    assert _rules(pos, "fixture.py") == []


def test_dist_op_unlowered_covers_multiway():
    """The instrumented ``dist_multiway_join`` entry point must keep its
    LOWERING case: with it present the fixture is quiet, and an
    uncovered sibling spelling still fires — the guard that stops the
    fused-join operator from silently falling off the optimized-plan
    surface as it evolves."""
    path = os.path.join(REPO, "cylon_tpu", "parallel", "zz_fixture.py")
    covered = ("from ..analysis import plan_check\n"
               "@plan_check.instrument\n"
               "def dist_multiway_join(fact, dims, edges):\n"
               "    return fact\n")
    assert _rules(covered, path) == []
    uncovered = covered.replace("dist_multiway_join",
                                "dist_multiway_join_v2")
    assert _rules(uncovered, path) == ["dist-op-unlowered"]
    # and the real executor table genuinely carries the key
    from cylon_tpu.plan.executor import LOWERING
    assert "dist_multiway_join" in LOWERING


def test_dist_op_unlowered_covers_groupby_fused():
    """The fused aggregation exchange keeps its LOWERING case: the
    covered fixture is quiet, an uncovered sibling spelling fires, and
    the real executor table carries the key (same guard as the multiway
    operator above)."""
    path = os.path.join(REPO, "cylon_tpu", "parallel", "zz_fixture.py")
    covered = ("from ..analysis import plan_check\n"
               "@plan_check.instrument\n"
               "def dist_groupby_fused(dt, key_columns, aggregations):\n"
               "    return dt\n")
    assert _rules(covered, path) == []
    uncovered = covered.replace("dist_groupby_fused",
                                "dist_groupby_fused_v2")
    assert _rules(uncovered, path) == ["dist-op-unlowered"]
    from cylon_tpu.plan.executor import LOWERING
    assert "dist_groupby_fused" in LOWERING


def test_counter_not_in_catalogue_fires_on_unknown_literal():
    pos = ("from .. import trace\n"
           "def f():\n"
           "    trace.count('totally.unknown_metric')\n")
    assert _rules(pos, "cylon_tpu/parallel/fixture.py") \
        == ["counter-not-in-catalogue"]
    sup = ("from .. import trace\n"
           "def f():\n"
           "    trace.count('totally.unknown_metric')"
           "  # graftlint: ok[counter-not-in-catalogue]\n")
    assert _rules(sup, "cylon_tpu/parallel/fixture.py") == []


def test_counter_not_in_catalogue_clean_spellings():
    # a catalogued name is clean, for all three bump kinds
    clean = ("from .. import trace\n"
             "def f():\n"
             "    trace.count('shuffle.exchanges')\n"
             "    trace.count_max('shuffle.exchange_bytes_peak', 9)\n"
             "    trace.gauge('serve.queue_depth', 3)\n")
    assert _rules(clean, "cylon_tpu/parallel/fixture.py") == []
    # dynamic names are the runtime compliance tests' job, not lint's
    dyn = ("from .. import trace\n"
           "from . import cost\n"
           "def f(choice):\n"
           "    trace.count(cost.strategy_counter(choice))\n")
    assert _rules(dyn, "cylon_tpu/parallel/fixture.py") == []
    # outside the tree (no cylon_tpu/ root to resolve the catalogue
    # from) the rule stays silent rather than guessing
    assert _rules("import t as trace\ntrace.count('x.y')\n",
                  "elsewhere/fixture.py") == []


def test_warn_once_key_literal_fires_on_dynamic_keys():
    # a bare variable key: every call is unique — the rate limit dies
    bad = ("from cylon_tpu import logging as glog\n"
           "def f(key):\n"
           "    glog.warn_once(key, 'm')\n")
    assert _rules(bad) == ["warn-once-key-literal"]
    # a tuple whose HEAD is dynamic is just as ungreppable
    bad2 = ("from cylon_tpu import logging as glog\n"
            "def f(rule, sig):\n"
            "    glog.warn_once((rule, sig), 'm')\n")
    assert _rules(bad2) == ["warn-once-key-literal"]
    # f-string keys are the classic spam shape
    bad3 = ("from cylon_tpu import logging as glog\n"
            "def f(q):\n"
            "    glog.warn_once(f'slo.{q}', 'm')\n")
    assert _rules(bad3) == ["warn-once-key-literal"]
    sup = ("from cylon_tpu import logging as glog\n"
           "def f(key):\n"
           "    glog.warn_once(key, 'm')"
           "  # graftlint: ok[warn-once-key-literal]\n")
    assert _rules(sup) == []


def test_warn_once_key_literal_clean_shapes():
    # the two sanctioned shapes: a literal, or a literal-headed tuple
    clean = ("from cylon_tpu import logging as glog\n"
             "def f(hint_key):\n"
             "    glog.warn_once('slo.p99-drift', 'm')\n"
             "    glog.warn_once(('shuffle.skew', hint_key), 'm')\n")
    assert _rules(clean) == []
    # an unrelated warn_once method on some other object is not glog's
    other = ("def f(log, key):\n"
             "    log.warn_once(key, 'm')\n")
    assert _rules(other) == []


def test_counter_not_in_catalogue_bare_names_only_in_trace_module():
    bare = "def g():\n    count('nope.metric')\n"
    assert _rules(bare, "cylon_tpu/trace.py") \
        == ["counter-not-in-catalogue"]
    # a bare count() anywhere else is some unrelated local function
    assert _rules(bare, "cylon_tpu/ops/fixture.py") == []


def test_counter_catalogue_parse_matches_runtime():
    """The AST-parsed catalogue (what lint checks against) must equal
    the imported METRICS (what the runtime compliance tests check
    against) — the two views cannot drift."""
    from cylon_tpu import observe
    names = graftlint._metric_names(
        os.path.join(REPO, "cylon_tpu", "parallel", "shuffle.py"))
    assert names is not None
    assert set(names) == set(observe.METRICS)


def test_fault_point_not_in_catalogue_fires_on_unknown_literal():
    pos = ("from .. import faults\n"
           "def f():\n"
           "    faults.check('totally.unknown_point')\n")
    assert _rules(pos, "cylon_tpu/parallel/fixture.py") \
        == ["fault-point-not-in-catalogue"]
    pos2 = ("from .. import faults\n"
            "def f(v):\n"
            "    return faults.perturb('nope.point', v)\n")
    assert _rules(pos2, "cylon_tpu/parallel/fixture.py") \
        == ["fault-point-not-in-catalogue"]
    sup = ("from .. import faults\n"
           "def f():\n"
           "    faults.check('totally.unknown_point')"
           "  # graftlint: ok[fault-point-not-in-catalogue]\n")
    assert _rules(sup, "cylon_tpu/parallel/fixture.py") == []


def test_fault_point_not_in_catalogue_clean_spellings():
    clean = ("from .. import faults\n"
             "def f(v):\n"
             "    faults.check('exec.stage')\n"
             "    faults.check('compact.read_counts')\n"
             "    return faults.perturb('resilience.budget', v)\n")
    assert _rules(clean, "cylon_tpu/parallel/fixture.py") == []
    # dynamic names are runtime coverage's job, not lint's
    dyn = ("from .. import faults\n"
           "def f(name):\n"
           "    faults.check(name)\n")
    assert _rules(dyn, "cylon_tpu/parallel/fixture.py") == []
    # an unrelated check() method on some other object is not faults'
    other = "def f(guard):\n    guard.check('whatever.point')\n"
    assert _rules(other, "cylon_tpu/parallel/fixture.py") == []


def test_fault_point_catalogue_parse_matches_runtime():
    """The AST-parsed POINTS (what lint checks against) must equal the
    imported faults.POINTS — the two views cannot drift."""
    from cylon_tpu import faults
    names = graftlint._fault_point_names(
        os.path.join(REPO, "cylon_tpu", "plan", "executor.py"))
    assert names is not None
    assert set(names) == set(faults.POINTS)


def test_ci_entry_point(tmp_path):
    """``python -m cylon_tpu.analysis.ci``: stage aggregation + the
    usage contract (the plan-check stage itself is covered by the
    repo-wide run in test_query_planner / the bench pre-flight)."""
    from cylon_tpu.analysis import ci
    # benchdiff needs both sides
    assert ci.main(["--baseline", "old.json"]) == 2
    # lint-only pass over the real tree is clean (stage 1 exit 0); the
    # hierarchy smoke is skipped here — its content is tier-1 covered
    # by tests/test_hierarchy.py, and re-running it inside this
    # aggregation check would only re-pay its 8-device exchange wall
    assert ci.main(["--no-plan-check", "--no-hierarchy-smoke"]) == 0


def test_ci_plan_check_counts_non_validation_crashes(monkeypatch):
    """A query that crashes OUTSIDE the validator (capture bug, bad
    column ref raising CylonError) is still a finding: the stage must
    keep the 0/1/2 exit contract instead of dying with a traceback and
    skipping the aggregated summary."""
    from cylon_tpu.analysis import ci
    from cylon_tpu.status import CylonError, Status, Code
    from cylon_tpu.tpch import queries

    def qbad(ctx, t):
        raise CylonError(Status(Code.KeyError, "no column 'nope'"))

    monkeypatch.setattr(queries, "QUERIES", {"qbad": qbad})
    assert ci._stage_plan_check(0.002) == 1


def test_cli_exits_nonzero_on_seeded_violation(tmp_path):
    bad = tmp_path / "seeded.py"
    bad.write_text("import jax.numpy as jnp\n"
                   "n = int(jnp.sum(dt.counts))\n")
    proc = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis.graftlint", str(bad)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "implicit-host-sync" in proc.stdout


def test_cli_parse_error_exits_2(tmp_path):
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    proc = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis.graftlint", str(broken)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2, proc.stdout + proc.stderr


def test_cli_usage_error():
    proc = subprocess.run(
        [sys.executable, "-m", "cylon_tpu.analysis.graftlint"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 2


# the snippets name jax's private modules through {core}/{src} so that
# this file, too, spells them nowhere (a plain grep stays a valid check)
@pytest.mark.parametrize("template", [
    "import jax\nok = jax.{core}.trace_state_clean()\n",
    "import jax\nT = jax.{src}.core.Tracer\n",
    "from jax.{src} import core\n",
    "from jax.{core} import Tracer\n",
    "from jax import {core}\n",
    "import jax.{src}.core as c\n",
])
def test_private_jax_api_fires_outside_the_shim(template):
    src = template.format(core="core", src="_src")
    assert _rules(src, "cylon_tpu/parallel/x.py") == ["private-jax-api"]
    assert _rules(src.rstrip("\n") + "  # graftlint: ok[private-jax-api]\n",
                  "cylon_tpu/parallel/x.py") == []


def test_private_jax_api_clean_in_shim_and_public_names():
    private = "from jax.{} import core as _core\nT = _core.Tracer\n" \
        .format("_src")
    assert _rules(private, "cylon_tpu/_jax_compat.py") == []
    public = ("import jax\nfrom jax import shard_map\n"
              "from cylon_tpu._jax_compat import trace_state_clean\n"
              "x = jax.numpy.zeros(3)\ny = jax.lax.psum\n")
    assert _rules(public, "cylon_tpu/parallel/x.py") == []


def test_repo_lints_clean():
    """The tier-1 gate: the tree itself must carry zero unsuppressed
    findings (every deliberate host boundary is allow-listed or carries
    a ``# graftlint: ok[...]`` comment explaining itself)."""
    findings = graftlint.lint_paths([os.path.join(REPO, "cylon_tpu"),
                                     os.path.join(REPO, "bench.py"),
                                     os.path.join(REPO, "chip_smoke.py")])
    assert findings == [], "\n".join(str(f) for f in findings)
