"""Tests for repro.analysis.staticcheck (DESIGN.md §13).

Every REPRO### rule gets a flagging fixture AND a non-flagging fixture;
the jaxpr layer is verified against deliberately-broken plan builders
(injected host callback, non-class-rounded shape, t_min double-apply —
the exact PR 5/6/7 regressions); suppression comments and the baseline
are honored; and the tree itself must be clean.
"""
import dataclasses
import textwrap

import jax
import pytest

from repro.analysis import staticcheck
from repro.analysis.staticcheck import astlint, jaxpr_checks
from repro.analysis.staticcheck.findings import (
    Baseline, BaselineEntry, Finding, parse_suppressions)
from repro.core import plan as plan_mod
from repro.core import tracking


def lint(code: str, path: str = "src/repro/core/x.py"):
    return astlint.lint_source(path, textwrap.dedent(code))


def codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# REPRO001 — falsy-or capacity defaults (the PR 5 cap=0 bug class)
# ---------------------------------------------------------------------------


class TestRepro001:
    def test_flags_falsy_or_default(self):
        # the exact PR 5 bug shape: cap=0 is a VALID width that `or`
        # silently replaces with the default
        fs = lint("""
            def resolve_cap(cap, n_events):
                return cap or n_events
        """)
        assert codes(fs) == ["REPRO001"]

    def test_flags_attribute_capacity(self):
        fs = lint("""
            def f(cfg, stream):
                width = cfg.cap_occ or 32
                return width
        """)
        assert codes(fs) == ["REPRO001"]

    def test_is_none_default_clean(self):
        fs = lint("""
            def resolve_cap(cap, n_events):
                return cap if cap is not None else n_events
        """)
        assert fs == []

    def test_truthiness_test_position_clean(self):
        # `if cap or tail_cap:` is a genuine truthiness test, not a default
        fs = lint("""
            def f(cap, tail_cap):
                if cap or tail_cap:
                    return 1
                while cap or tail_cap:
                    break
                assert cap or tail_cap
                return 0
        """)
        assert fs == []

    def test_non_capacity_names_clean(self):
        fs = lint("""
            def f(name, fallback):
                return name or fallback
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# REPRO002 — unthreaded interpret/tile knobs
# ---------------------------------------------------------------------------


class TestRepro002:
    def test_flags_swallowed_knob(self):
        fs = lint("""
            def track(x, interpret=False, block_next=256):
                return run(x, block_next=block_next)
        """)
        assert codes(fs) == ["REPRO002"]
        assert "interpret" in fs[0].message

    def test_threaded_knob_clean(self):
        fs = lint("""
            def track(x, interpret=False, block_next=256):
                return run(x, block_next=block_next, interpret=interpret)
        """)
        assert fs == []

    def test_protocol_stub_clean(self):
        fs = lint("""
            def track(x, interpret=False):
                ...

            def track2(x, chunk=8):
                raise NotImplementedError
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# REPRO003 — jit/pallas_call outside the dispatch spine
# ---------------------------------------------------------------------------


class TestRepro003:
    def test_flags_direct_jit_call(self):
        fs = lint("""
            import jax
            def f(fn):
                return jax.jit(fn)
        """)
        assert codes(fs) == ["REPRO003"]

    def test_flags_jit_decorator(self):
        fs = lint("""
            import jax
            @jax.jit
            def f(x):
                return x
        """)
        assert codes(fs) == ["REPRO003"]

    def test_flags_partial_jit(self):
        fs = lint("""
            import functools, jax
            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                return x
        """)
        assert codes(fs) == ["REPRO003"]

    def test_flags_pallas_call(self):
        fs = lint("""
            from jax.experimental import pallas as pl
            def f(kernel, spec):
                return pl.pallas_call(kernel, out_shape=spec)
        """)
        assert codes(fs) == ["REPRO003"]

    def test_spine_paths_allowed(self):
        code = """
            import jax
            def f(fn):
                return jax.jit(fn)
        """
        assert lint(code, path="src/repro/core/plan.py") == []
        assert lint(code, path="src/repro/kernels/episode_track.py") == []

    def test_dispatch_clean(self):
        fs = lint("""
            from repro.core import plan
            def f(p, *args):
                return plan.dispatch(p, *args)
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# REPRO004 — syncs inside loop bodies
# ---------------------------------------------------------------------------


class TestRepro004:
    def test_flags_device_get_in_loop(self):
        fs = lint("""
            import jax
            def mine(levels):
                for level in levels:
                    counts = jax.device_get(level)
        """)
        assert codes(fs) == ["REPRO004"]

    def test_flags_block_until_ready_in_while(self):
        fs = lint("""
            def wait(x):
                while True:
                    x.block_until_ready()
        """)
        assert codes(fs) == ["REPRO004"]

    def test_sync_outside_loop_clean(self):
        fs = lint("""
            import jax
            def fetch(dev):
                return jax.device_get(dev)
        """)
        assert fs == []

    def test_closure_resets_loop_depth(self):
        # a helper *defined* inside a loop is not itself a loop-body sync
        fs = lint("""
            import jax
            def f(items):
                for it in items:
                    def fetch(x):
                        return jax.device_get(x)
                return fetch
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# REPRO005 — unregistered registry candidates
# ---------------------------------------------------------------------------


class TestRepro005:
    def test_flags_unregistered_builder(self):
        fs = lint("""
            from repro.core import plan as plan_mod
            def _build_good(p):
                return p
            def _specs_good(p):
                return ()
            def _build_orphan(p):
                return p
            plan_mod.register_fn("good", _build_good, _specs_good)
        """)
        assert codes(fs) == ["REPRO005"]
        assert "_build_orphan" in fs[0].message

    def test_flags_unregistered_engine(self):
        fs = lint("""
            from repro.core.tracking import register_engine
            class GoodEngine:
                name = "good"
            class OrphanEngine:
                name = "orphan"
            register_engine(GoodEngine())
        """)
        assert codes(fs) == ["REPRO005"]
        assert "OrphanEngine" in fs[0].message

    def test_protocol_class_clean(self):
        fs = lint("""
            from typing import Protocol
            from repro.core.tracking import register_engine
            class TrackingEngine(Protocol):
                name: str
            class RealEngine:
                name = "real"
            register_engine(RealEngine())
        """)
        assert fs == []

    def test_module_without_registration_clean(self):
        # helper names are only registry candidates in registering modules
        fs = lint("""
            def _build_table(rows):
                return rows
        """)
        assert fs == []


# ---------------------------------------------------------------------------
# REPRO006 / REPRO007 — mechanical hygiene
# ---------------------------------------------------------------------------


class TestMechanicalRules:
    def test_flags_trailing_whitespace(self):
        fs = astlint.lint_text("x.py", "a = 1 \nb = 2\n")
        assert codes(fs) == ["REPRO006"]
        assert fs[0].line == 1

    def test_flags_tab(self):
        fs = astlint.lint_text("x.py", "def f():\n\treturn 1\n")
        assert codes(fs) == ["REPRO007"]

    def test_clean_text(self):
        assert astlint.lint_text("x.py", "a = 1\nb = 2\n") == []

    def test_runs_on_non_python_files(self):
        fs = astlint.lint_text("config.yml", "key: value \n")
        assert codes(fs) == ["REPRO006"]


# ---------------------------------------------------------------------------
# suppression + baseline policy
# ---------------------------------------------------------------------------


class TestSuppression:
    def test_same_line_suppression(self):
        fs = lint("""
            import jax
            def f(fn):
                return jax.jit(fn)  # staticcheck: disable=REPRO003 -- why
        """)
        kept, muted = staticcheck.filter_findings(
            fs, sources={"src/repro/core/x.py": textwrap.dedent("""
            import jax
            def f(fn):
                return jax.jit(fn)  # staticcheck: disable=REPRO003 -- why
        """)}, baseline=Baseline([]))
        assert kept == []
        assert codes(muted) == ["REPRO003"]

    def test_standalone_comment_covers_next_code_line(self):
        src = textwrap.dedent("""
            import jax
            def f(fn):
                # staticcheck: disable=REPRO003 -- sanctioned bypass,
                # explained across two comment lines
                return jax.jit(fn)
        """)
        supp = parse_suppressions(src)
        fs = astlint.lint_source("x.py", src)
        assert all(f.line in supp and "REPRO003" in supp[f.line]
                   for f in fs if f.code == "REPRO003")

    def test_wrong_code_does_not_suppress(self):
        src = "a = 1  # staticcheck: disable=REPRO006\n"
        kept, muted = staticcheck.filter_findings(
            [Finding("x.py", 1, "REPRO007", "tab")],
            sources={"x.py": src}, baseline=Baseline([]))
        assert codes(kept) == ["REPRO007"]

    def test_baseline_exempts_by_path_and_code(self):
        bl = Baseline([BaselineEntry("src/repro/models/", ("REPRO003",),
                                     "seed scaffolding")])
        exempt = Finding("src/repro/models/model.py", 3, "REPRO003", "m")
        kept_f = Finding("src/repro/models/model.py", 3, "REPRO006", "m")
        kept, muted = staticcheck.filter_findings(
            [exempt, kept_f], sources={}, baseline=bl)
        assert codes(kept) == ["REPRO006"]
        assert codes(muted) == ["REPRO003"]

    def test_checked_in_baseline_never_mutes_mechanical_rules(self):
        # policy: REPRO006/REPRO007 run blocking on every file
        bl = staticcheck.load_baseline()
        for entry in bl.entries:
            assert "REPRO006" not in entry.codes
            assert "REPRO007" not in entry.codes
            assert "*" not in entry.codes


# ---------------------------------------------------------------------------
# Layer 1 — jaxpr checks against deliberately-broken builders
# ---------------------------------------------------------------------------


def _register_wrapped(name: str, wrap):
    """Register a counting fn that wraps count_indexed's traced body."""
    entry = plan_mod._fn_entry("count_indexed")

    def build(p):
        return wrap(entry.build(p))

    plan_mod.register_fn(name, build, entry.specs)
    return plan_mod.plan_for(name, level=3, n_types=8, cap=256, batch=8,
                             engine="dense", interpret=True)


@pytest.fixture
def scratch_registry():
    """Temporary fns registered by a test are dropped afterwards."""
    before = set(plan_mod._FNS)
    yield
    for name in set(plan_mod._FNS) - before:
        del plan_mod._FNS[name]


class TestJaxprLayer:
    def test_clean_plan_passes(self):
        p = plan_mod.plan_for("count_indexed", level=3, n_types=8, cap=256,
                              batch=8, engine="dense", interpret=True)
        assert jaxpr_checks.check_plan(p) == []

    def test_injected_host_callback_flags(self, scratch_registry):
        def wrap(fn):
            def bad(*args):
                jax.debug.callback(lambda: None)
                return fn(*args)
            return bad

        p = _register_wrapped("bad_cb", wrap)
        assert "REPRO101" in codes(jaxpr_checks.check_plan(p))

    def test_non_class_rounded_cap_flags(self):
        good = plan_mod.plan_for("count_indexed", level=3, n_types=8,
                                 cap=256, batch=8, engine="dense",
                                 interpret=True)
        bad = dataclasses.replace(good, cap=100)
        entry = plan_mod._fn_entry("count_indexed")
        fs = jaxpr_checks.check_rounding(bad, entry.specs(bad))
        assert "REPRO102" in codes(fs)

    def test_non_pow2_batch_flags(self):
        good = plan_mod.plan_for("count_indexed", level=3, n_types=8,
                                 cap=256, batch=8, engine="dense",
                                 interpret=True)
        bad = dataclasses.replace(good, batch=7)
        entry = plan_mod._fn_entry("count_indexed")
        fs = jaxpr_checks.check_rounding(bad, entry.specs(bad))
        assert "REPRO102" in codes(fs)

    def test_tmin_double_apply_flags(self, scratch_registry):
        # the PR 6 hazard: a builder applying the seed restriction itself
        # ON TOP of the t_min consume_seed_restriction performs
        def wrap(fn):
            def bad(table, *rest):
                table = tracking.restrict_seed_row(table[None], 0.0)[0]
                return fn(table, *rest)
            return bad

        p = _register_wrapped("bad_tmin", wrap)
        fs = jaxpr_checks.check_plan(p)
        assert "REPRO103" in codes(fs)

    def test_count_tail_applies_tmin_exactly_once(self):
        p = plan_mod.plan_for("count_tail", level=3, n_types=8, cap=256,
                              batch=8, tail_cap=64, engine="dense",
                              interpret=True)
        _closed, n = jaxpr_checks.trace_plan(p)
        assert n == 1
        assert jaxpr_checks.check_tmin(p, n) == []
        assert jaxpr_checks.check_tmin(p, 2) != []

    def test_tile_contract_flags_overbudget_vmem(self):
        # a chip launch whose per-row times block alone (4 rows of 4M
        # events, double-buffered) is far past the VMEM budget
        fs = jaxpr_checks._tile_contract(
            "plan://synthetic", "count", 3, 1 << 22, 64, 256, 256, 0, 64)
        assert "REPRO104" in codes(fs)

    def test_tuned_table_clean(self):
        assert jaxpr_checks.check_tuned_table() == []

    def test_default_matrix_covers_every_fn_and_engine(self):
        plans = jaxpr_checks.default_matrix()
        fns = {p.fn for p in plans}
        engines = {p.engine for p in plans}
        assert fns == set(plan_mod._FNS)
        assert engines == set(tracking.engine_names())


# ---------------------------------------------------------------------------
# tree-is-clean smoke + runner plumbing
# ---------------------------------------------------------------------------


class TestTree:
    def test_lint_layer_tree_is_clean(self):
        report = staticcheck.run(jaxpr=False)
        assert report["ok"], report["text"]

    def test_default_matrix_tree_is_clean(self):
        report = staticcheck.run(matrix="default")
        assert report["ok"], report["text"]
        assert report["plans_checked"] > 0

    def test_report_json_roundtrip(self):
        import json
        report = staticcheck.run(jaxpr=False)
        blob = json.loads(staticcheck.report_json(report))
        assert blob["ok"] is True
        assert blob["files_checked"] == report["files_checked"]

    def test_changed_files_subset_of_tree(self):
        root = staticcheck.runner.repo_root()
        tree = set(staticcheck.discover_files(root))
        for rel in staticcheck.changed_files(root):
            assert rel in tree
