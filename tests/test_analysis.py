"""Static program auditor (repro.analysis): known-bad fixtures per
pass, the clean audit over the full engine registry, and the
baseline-compare regression gate.

Every fixture here is a program with exactly the defect the pass
claims to catch — if a lint rule rots, the fixture stops failing and
this file catches it. The sharded fixtures re-run for real under the
forced-8-device tier-1 leg.
"""
import jax
import jax.experimental
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis import registry
from repro.analysis.hlo_audit import audit_hlo
from repro.analysis.jaxpr_lint import lint_jaxpr, trace_closed_jaxpr
from repro.analysis.registry import DEFAULT_INVARIANTS as INV
from repro.analysis.run import audit_engine, compare, run_audit
from repro.analysis.source_lint import lint_source
from repro.launch.mesh import make_shard_mesh


def _jaxpr_checks(fn, args):
    v, census = lint_jaxpr(trace_closed_jaxpr(fn, args, {}), INV)
    return [x["check"] for x in v], census


# ---------------------------------------------------------------------------
# pass 1: jaxpr lint
# ---------------------------------------------------------------------------

def test_callback_under_scan_flagged():
    def step(c, x):
        jax.debug.callback(lambda v: None, x)
        return c + x, c

    fn = jax.jit(lambda xs: jax.lax.scan(step, jnp.float32(0), xs))
    checks, _ = _jaxpr_checks(fn, (jnp.ones(4, jnp.float32),))
    assert "host_callback" in checks


def test_f64_leak_flagged():
    with jax.enable_x64(True):
        fn = jax.jit(lambda x: x.astype(jnp.float64) * 2)
        checks, _ = _jaxpr_checks(fn, (jnp.ones(3, jnp.float32),))
    assert "f64" in checks


def test_clip_scatter_flagged_and_counted():
    fn = jax.jit(lambda x, i, u: x.at[i].set(u, mode="clip"))
    checks, census = _jaxpr_checks(
        fn, (jnp.zeros(8), jnp.array([2]), jnp.ones(1)))
    assert "scatter_mode" in checks
    assert census["totals"]["scatter_ops"] == 1


def test_default_drop_scatter_clean():
    # .at[].set() without mode defaults to FILL_OR_DROP — the semantics
    # ShardedStore's routed append relies on; must NOT be flagged
    fn = jax.jit(lambda x, i, u: x.at[i].set(u))
    checks, census = _jaxpr_checks(
        fn, (jnp.zeros(8), jnp.array([2]), jnp.ones(1)))
    assert checks == []
    assert census["totals"]["scatter_ops"] == 1


def test_weak_output_flagged():
    fn = jax.jit(lambda x: jnp.asarray(1.0) * 1.0)
    checks, _ = _jaxpr_checks(fn, (jnp.ones(3),))
    assert "weak_type_output" in checks


def test_scan_census_multiplies_trips():
    def step(c, x):
        return c.at[jnp.int32(0)].add(x), x

    fn = jax.jit(lambda xs: jax.lax.scan(step, jnp.zeros(2), xs))
    _, census = _jaxpr_checks(fn, (jnp.ones(7, jnp.float32),))
    t = census["totals"]
    assert t["scatter_ops"] == 1          # one scatter eqn in the body
    assert t["scatter_executed"] == 7     # executed once per scan trip


# ---------------------------------------------------------------------------
# pass 2: HLO audit
# ---------------------------------------------------------------------------

def test_hlo_host_callback_flagged():
    fn = jax.jit(lambda x: jax.pure_callback(
        lambda a: np.asarray(a) * 2,
        jax.ShapeDtypeStruct((3,), jnp.float32), x))
    hlo = fn.lower(jnp.ones(3, jnp.float32)).compile().as_text()
    v, _ = audit_hlo(hlo, INV)
    assert "host_transfer" in [x["check"] for x in v]


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 devices")
def test_unbalanced_collective_flagged():
    mesh = make_shard_mesh(2)

    def body(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, "shard"),
                            lambda v: v * 2.0, x)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("shard"),
                           out_specs=P("shard"), check_vma=False))
    hlo = fn.lower(jnp.ones((4, 2))).compile().as_text()
    v, _ = audit_hlo(hlo, INV)
    assert "unbalanced_collective" in [x["check"] for x in v]


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >=2 devices")
def test_balanced_collective_clean():
    mesh = make_shard_mesh(2)

    def body(x):
        return jax.lax.psum(x, "shard")   # unconditional: every shard

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("shard"),
                           out_specs=P(), check_vma=False))
    hlo = fn.lower(jnp.ones((4, 2))).compile().as_text()
    v, info = audit_hlo(hlo, INV)
    assert v == []
    assert sum(info["op_counts"]["collective_counts"].values()) >= 1


# ---------------------------------------------------------------------------
# pass 3: source lint
# ---------------------------------------------------------------------------

def _source_checks(text):
    v, _ = lint_source(text, "fixture")
    return [x["check"] for x in v]


def test_np_call_under_jit_flagged():
    assert "np_call_in_jit" in _source_checks(
        "import jax\nimport numpy as np\n"
        "@jax.jit\ndef f(x):\n    return np.sum(x)\n")


def test_np_call_under_scan_body_flagged():
    # reaches the traced set through lax.scan, not a jit decorator
    assert "np_call_in_jit" in _source_checks(
        "import jax\nimport numpy as np\n"
        "def step(c, x):\n    return c, np.log(x)\n"
        "@jax.jit\ndef f(xs):\n"
        "    return jax.lax.scan(step, 0.0, xs)\n")


def test_python_branch_on_operand_flagged():
    assert "python_branch_on_operand" in _source_checks(
        "import jax\n@jax.jit\ndef f(x):\n"
        "    if x > 0:\n        return x\n    return -x\n")


def test_branch_on_static_argname_clean():
    assert _source_checks(
        "import jax\nfrom functools import partial\n"
        "@partial(jax.jit, static_argnames=('mode',))\n"
        "def f(x, mode):\n"
        "    if mode == 2:\n        return x\n    return -x\n") == []


def test_string_compare_dispatch_clean():
    # `op == 'ge'` style trace-time dispatch (query._int_pred) is fine
    assert _source_checks(
        "import jax\n@jax.jit\ndef f(x, op):\n"
        "    if op == 'ge':\n        return x\n    return -x\n") == []


def test_global_in_jit_flagged():
    assert "global_in_jit" in _source_checks(
        "import jax\n@jax.jit\ndef f(x):\n"
        "    global _g\n    _g = x\n    return x\n")


def test_unhashable_static_default_flagged():
    assert "unhashable_static_default" in _source_checks(
        "import jax\nfrom functools import partial\n"
        "@partial(jax.jit, static_argnames=('cfg',))\n"
        "def f(x, cfg=[1]):\n    return x\n")


def test_static_name_missing_flagged():
    assert "static_name_missing" in _source_checks(
        "import jax\nfrom functools import partial\n"
        "@partial(jax.jit, static_argnames=('mode',))\n"
        "def f(x):\n    return x\n")


def test_missing_docstring_flagged_in_scoped_modules():
    src = ("def public(x):\n    return x\n"
           "class Thing:\n    pass\n"
           "def _private(x):\n    return x\n")
    v, _ = lint_source(src, "repro.core.fake")
    flagged = [x["path"] for x in v if x["check"] == "missing_docstring"]
    assert flagged == ["repro.core.fake:public:1", "repro.core.fake:Thing:3"]
    # unscoped modules don't get the rule
    v2, _ = lint_source(src, "repro.runtime.fake")
    assert [x for x in v2 if x["check"] == "missing_docstring"] == []


def test_docstring_present_clean():
    src = ('def public(x):\n    """Doc."""\n    return x\n'
           'class Thing:\n    """Doc."""\n')
    v, _ = lint_source(src, "repro.warehouse.fake")
    assert [x for x in v if x["check"] == "missing_docstring"] == []


def test_jit_defs_module_level_only():
    _, defs = lint_source(
        "import jax\n"
        "@jax.jit\ndef top(x):\n    return x\n"
        "def factory():\n"
        "    @jax.jit\n    def nested(x):\n        return x\n"
        "    return nested\n"
        "bound = jax.jit(factory)\n", "fixture")
    assert defs == {"fixture:top", "fixture:bound"}


# ---------------------------------------------------------------------------
# the registry + driver
# ---------------------------------------------------------------------------

def _toy_engine(**kw):
    inv = dict(INV)
    inv.update(kw.pop("invariants", {}))
    fn = jax.jit(lambda x: x * 2)
    return registry.Engine(
        "toy", kw.pop("build", lambda: registry.EngineExample(
            fn, (jnp.ones(3, jnp.float32),), {})),
        inv, kw.pop("probe", lambda: fn._cache_size()), ())


def test_missing_probe_is_violation():
    rec = audit_engine(_toy_engine(probe=None))
    assert "missing_probe" in [v["check"] for v in rec["violations"]]


def test_dispatch_cap_enforced():
    rec = audit_engine(_toy_engine(invariants={"max_new_executables": 0}))
    assert "dispatch_count" in [v["check"] for v in rec["violations"]]


def test_skip_engine_recorded():
    def build():
        raise registry.SkipEngine("needs 8 devices")

    rec = audit_engine(_toy_engine(build=build))
    assert rec["skipped"] == "needs 8 devices"
    assert rec["violations"] == []


def test_clean_audit_full_registry():
    """The tier-1 gate: every registered engine passes all three passes
    and every module-level jitted def in core/ / warehouse/ /
    distribution/ is covered by some engine."""
    report = run_audit()
    assert report["n_violations"] == 0, report["violations"]
    assert len(report["engines"]) >= 30
    # census actually quantifies the scatter floor per plan shape
    census = report["engines"]["warehouse_query_filter_groupby"][
        "jaxpr_census"]["totals"]
    assert census["scatter_ops"] >= 1


def test_compare_flags_dispatch_growth():
    old = {"topology": {"n_devices": 1}, "n_violations": 0,
           "engines": {"e": {"dispatch": {"new_executables": 1}}}}
    new = {"topology": {"n_devices": 1}, "n_violations": 0,
           "engines": {"e": {"dispatch": {"new_executables": 2}}}}
    assert any("dispatch count grew" in r for r in compare(new, old))
    assert compare(old, old) == []


def test_compare_flags_new_violations_and_lost_engines():
    old = {"topology": {"n_devices": 1}, "n_violations": 0,
           "engines": {"e": {"dispatch": {"new_executables": 1}}}}
    bad = {"topology": {"n_devices": 1}, "n_violations": 2,
           "engines": {"e": {"dispatch": {"new_executables": 1}}}}
    assert any("violations" in r for r in compare(bad, old))
    gone = {"topology": {"n_devices": 1}, "n_violations": 0, "engines": {}}
    assert any("disappeared" in r for r in compare(gone, old))


def test_compare_skips_dispatch_on_topology_change():
    old = {"topology": {"n_devices": 1}, "n_violations": 0,
           "engines": {"e": {"dispatch": {"new_executables": 1}}}}
    new = {"topology": {"n_devices": 8}, "n_violations": 0,
           "engines": {"e": {"dispatch": {"new_executables": 3}}}}
    assert compare(new, old) == []       # growth excused, not a lie:
    # violations still count under any topology
    new["n_violations"] = 1
    assert len(compare(new, old)) == 1
