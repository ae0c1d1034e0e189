"""flylint test suite (docs/static-analysis.md).

Three layers:

1. **Checker fixtures** — a positive trip, a negative pass, and a
   suppression case per rule, against purpose-built mini-projects in
   tmp_path (the registry rules get a full fixture tree: appconfig +
   docs + faults + exceptions + app).
2. **Framework** — baseline round-trip (accept -> clean -> stale), CLI
   exit codes, and the self-check: the REAL repo must scan clean (this
   pins every drift fix in this PR — reintroducing one fails tier-1,
   not just the CI lint job).
3. **Lock-order witness** — scoped AB/BA seeded-deadlock self-test (the
   report must carry both acquisition stacks), RLock/Condition
   bookkeeping, and an end-to-end subprocess pytest run proving the
   conftest plugin fails a session on a seeded cycle.

Plus regression tests for the real findings fixed in this PR (executor
heal / refresh-queue thread starts moved outside their locks, the
MissingParamsException mapping, the application_name knob wiring).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from tools.flylint.checkers import ALL_CHECKERS, ALL_RULES
from tools.flylint.checkers.concurrency import ConcurrencyChecker
from tools.flylint.checkers.jax_hazards import JaxHazardsChecker
from tools.flylint.checkers.observability import ObservabilityChecker
from tools.flylint.checkers.registry import RegistryChecker
from tools.flylint.core import (
    Project,
    load_baseline,
    run_checkers,
    write_baseline,
)
from tools.flylint.witness import LockOrderWitness

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(root, relpath, text):
    path = os.path.join(str(root), relpath)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(textwrap.dedent(text))
    return path


def _scan(root, paths=("flyimg_tpu",), checkers=None, baseline=None):
    project = Project(str(root), list(paths))
    return run_checkers(
        project, checkers or ALL_CHECKERS, baseline or {}
    )


def _rules(result):
    return {f.rule for f in result.findings}


# ---------------------------------------------------------------------------
# concurrency checker


def _conc(root, body):
    _write(root, "flyimg_tpu/mod.py", body)
    return _scan(root, checkers=[ConcurrencyChecker()])


def test_sleep_under_lock_trips(tmp_path):
    result = _conc(tmp_path, """\
        import threading, time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def work(self):
                with self._lock:
                    time.sleep(1)
        """)
    assert _rules(result) == {"lock-held-blocking-call"}
    (f,) = result.findings
    assert "time.sleep" in f.message and f.symbol == "C.work"


def test_result_and_get_without_timeout_trip(tmp_path):
    result = _conc(tmp_path, """\
        class C:
            def work(self, fut, q, d):
                with self._lock:
                    fut.result()
                    q.get()
                    d.get("key")          # dict-style get: fine
                    q.get(timeout=1.0)    # bounded: fine
                    fut.result(timeout=2) # bounded: fine
        """)
    msgs = [f.message for f in result.findings]
    assert len(msgs) == 2
    assert any("Future" in m for m in msgs)
    assert any("queue" in m for m in msgs)


def test_thread_start_and_join_under_lock_trip(tmp_path):
    result = _conc(tmp_path, """\
        class C:
            def work(self):
                with self._lock:
                    self._thread.start()
                    self._thread.join()
        """)
    assert len(result.findings) == 2
    assert _rules(result) == {"lock-held-blocking-call"}


def test_io_under_lock_trips_and_clean_section_passes(tmp_path):
    result = _conc(tmp_path, """\
        import httpx

        class C:
            def bad(self):
                with self._lock:
                    httpx.get("http://x")

            def good(self):
                with self._lock:
                    self.counter += 1
                    self.table["k"] = 2
        """)
    (f,) = result.findings
    assert f.symbol == "C.bad"


def test_condition_wait_on_held_lock_passes(tmp_path):
    result = _conc(tmp_path, """\
        class C:
            def work(self, other):
                with self._lock:
                    self._lock.wait()   # releases the held lock: fine
                with self._lock:
                    other.wait()        # some OTHER event: blocks
        """)
    (f,) = result.findings
    assert "event/condition" in f.message


def test_locked_suffix_convention_checked(tmp_path):
    result = _conc(tmp_path, """\
        import time

        class C:
            def _heal_locked(self):
                time.sleep(0.5)

            def helper(self):
                time.sleep(0.5)  # not *_locked, no lexical lock: fine
        """)
    (f,) = result.findings
    assert f.symbol == "C._heal_locked"


def test_one_hop_self_call_blocking_trips(tmp_path):
    result = _conc(tmp_path, """\
        class C:
            def _spawn(self):
                self._thread.start()

            def submit(self):
                with self._lock:
                    self._spawn()
        """)
    assert any(
        "self._spawn()" in f.message and f.symbol == "C.submit"
        for f in result.findings
    )


def test_double_acquire_trips_and_distinct_locks_pass(tmp_path):
    result = _conc(tmp_path, """\
        class C:
            def bad(self):
                with self._lock:
                    with self._lock:
                        pass

            def good(self):
                with self._lock:
                    with self._other_lock:
                        pass
        """)
    (f,) = result.findings
    assert f.rule == "lock-double-acquire" and f.symbol == "C.bad"


def test_suppression_same_line_and_line_above(tmp_path):
    result = _conc(tmp_path, """\
        import time

        class C:
            def a(self):
                with self._lock:
                    time.sleep(1)  # flylint: disable=lock-held-blocking-call

            def b(self):
                with self._lock:
                    # flylint: disable=lock-held-blocking-call
                    time.sleep(1)

            def c(self):
                with self._lock:
                    time.sleep(1)  # flylint: disable=some-other-rule
        """)
    assert len(result.findings) == 1  # only c's wrong-rule suppression
    assert result.findings[0].symbol == "C.c"
    assert result.suppressed == 2


def test_file_level_suppression(tmp_path):
    result = _conc(tmp_path, """\
        # flylint: disable-file=lock-held-blocking-call
        import time

        class C:
            def a(self):
                with self._lock:
                    time.sleep(1)
        """)
    assert not result.findings and result.suppressed == 1


# ---------------------------------------------------------------------------
# registry checker


def _registry_fixture(root):
    _write(root, "flyimg_tpu/appconfig.py", """\
        SERVER_DEFAULTS = {
            "good_knob": 1,
            "unread_knob": 2,
            "undocumented_knob": 3,
        }
        """)
    _write(root, "flyimg_tpu/exceptions.py", """\
        class AppException(Exception):
            pass

        class GoodException(AppException):
            pass

        class UnmappedException(AppException):
            pass
        """)
    _write(root, "flyimg_tpu/testing/faults.py", """\
        KNOWN_POINTS = frozenset({"fetch.http", "unused.point",
                                  "storage.read"})
        """)
    _write(root, "flyimg_tpu/service/app.py", """\
        from flyimg_tpu.testing import faults

        _ERROR_STATUS = {
            GoodException: 400,
            GhostException: 500,
        }

        def make_app(params, metrics, op):
            params.by_key("good_knob", 1)
            params.by_key("undocumented_knob", 3)
            params.by_key("mystery_knob", 9)
            faults.fire("fetch.http")
            faults.fire("rogue.point")
            faults.fire(f"storage.{op}")
            metrics.counter("flyimg_documented_total", "h")
            metrics.counter("flyimg_rogue_total", "h")
            metrics.counter('flyimg_shape_total{a="x"}', "h")
            metrics.counter(f'flyimg_shape_total{{b="{op}"}}', "h")
            metrics.counter('flyimg_labeled_total{reason="x"}', "h")
        """)
    _write(root, "docs/application-options.md", """\
        | Key | Default | Used by |
        |-----|---------|---------|
        | `good_knob` | `1` | testing |
        | `unread_knob` | `2` | testing |
        | `ghost_knob` | `0` | testing |
        """)
    _write(root, "docs/observability.md", """\
        | `flyimg_documented_total` | – | documented |
        | `flyimg_labeled_total` | – | emitted with a label this row omits |
        | `flyimg_ghost_total` | – | no flyimg_tpu/ emission site |
        | `flyimg_wild_*` | – | wildcard reference, never flagged |
        """)


def test_registry_rules_trip_together(tmp_path):
    _registry_fixture(tmp_path)
    result = _scan(tmp_path, checkers=[RegistryChecker()])
    by_rule = {}
    for f in result.findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {
        "knob-undeclared", "knob-unread", "knob-undocumented",
        "knob-doc-unknown", "fault-point-undeclared",
        "fault-point-unused", "metric-undocumented",
        "metric-inconsistent", "metrics-doc-parity",
        "exception-unmapped", "exception-map-unknown",
    }
    assert "mystery_knob" in by_rule["knob-undeclared"][0].message
    assert "unread_knob" in by_rule["knob-unread"][0].message
    assert "undocumented_knob" in by_rule["knob-undocumented"][0].message
    assert "ghost_knob" in by_rule["knob-doc-unknown"][0].message
    assert "rogue.point" in by_rule["fault-point-undeclared"][0].message
    assert "unused.point" in by_rule["fault-point-unused"][0].message
    assert "flyimg_rogue_total" in by_rule["metric-undocumented"][0].message
    assert "flyimg_shape_total" in by_rule["metric-inconsistent"][0].message
    parity = {f.message for f in by_rule["metrics-doc-parity"]}
    # doc -> code: a documented family with no emission site
    assert any("flyimg_ghost_total" in m for m in parity)
    # code -> doc: an emitted label key the family's doc row omits
    assert any(
        "flyimg_labeled_total" in m and "`reason`" in m for m in parity
    )
    # the wildcard reference is a family-set pointer, not a family
    assert not any("flyimg_wild_" in m for m in parity)
    assert "UnmappedException" in by_rule["exception-unmapped"][0].message
    assert "GhostException" in by_rule["exception-map-unknown"][0].message
    # the dynamic f-string fault point resolved against declared prefixes:
    # storage.read counts as fired, no undeclared finding for it
    assert not any(
        "storage." in f.message for f in by_rule["fault-point-undeclared"]
    )


def test_registry_clean_fixture_passes(tmp_path):
    _write(tmp_path, "flyimg_tpu/appconfig.py", """\
        SERVER_DEFAULTS = {"good_knob": 1}
        """)
    _write(tmp_path, "flyimg_tpu/service/app.py", """\
        def make_app(params):
            params.by_key("good_knob", 1)
        """)
    _write(tmp_path, "docs/application-options.md", """\
        | Key | Default | Used by |
        | `good_knob` | `1` | testing |
        """)
    result = _scan(tmp_path, checkers=[RegistryChecker()])
    assert not result.findings


def _telemetry_fixture(root, doc_text):
    _write(root, "flyimg_tpu/runtime/telemetry.py", """\
        RECORD_SCHEMAS = {
            "boot": ("schema", "kind", "undocumented_field"),
            "window": ("schema", "mix"),
        }
        """)
    _write(root, "docs/observability.md", doc_text)


def test_telemetry_schema_parity_trips_both_ways(tmp_path):
    _telemetry_fixture(tmp_path, """\
        ### Archive record schema

        | Kind | Fields | Meaning |
        |------|--------|---------|
        | `boot` | `schema`, `kind` | envelope |
        | `window` | `schema`, `mix` | the mix stamp |
        | `window` | `ghost_field` | documented but never emitted |

        ### Next section

        | `boot` | `outside_section` | rows past the heading are ignored |
        """)
    result = _scan(tmp_path, checkers=[RegistryChecker()])
    by_rule = {}
    for f in result.findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {
        "telemetry-field-undocumented", "telemetry-doc-unknown",
    }
    # code -> doc: the undocumented field, anchored at its schema entry
    undoc = by_rule["telemetry-field-undocumented"]
    assert len(undoc) == 1
    assert "boot.undocumented_field" in undoc[0].message
    assert undoc[0].path == "flyimg_tpu/runtime/telemetry.py"
    # doc -> code: the ghost row, anchored at the doc line; the row
    # outside the section is NOT parsed (no `boot.outside_section`)
    ghost = by_rule["telemetry-doc-unknown"]
    assert len(ghost) == 1
    assert "window.ghost_field" in ghost[0].message
    assert ghost[0].path == "docs/observability.md"


def test_telemetry_schema_parity_clean_fixture_passes(tmp_path):
    _telemetry_fixture(tmp_path, """\
        ### Archive record schema

        | Kind | Fields | Meaning |
        |------|--------|---------|
        | `boot` | `schema`, `kind`, `undocumented_field` | envelope |
        | `window` | `schema`, `mix` | the mix stamp |
        """)
    result = _scan(tmp_path, checkers=[RegistryChecker()])
    assert not result.findings


def test_telemetry_parity_inert_without_module(tmp_path):
    # the rule family must stay silent on projects without
    # runtime/telemetry.py (every other registry fixture run)
    _write(tmp_path, "flyimg_tpu/other.py", """\
        X = 1
        """)
    _write(tmp_path, "docs/observability.md", """\
        ### Archive record schema

        | `boot` | `schema` | no telemetry module in this project |
        """)
    result = _scan(tmp_path, checkers=[RegistryChecker()])
    assert not result.findings


def _chaos_fixture(root, campaign, *, suppress=""):
    _write(root, "flyimg_tpu/testing/faults.py", f"""\
        KNOWN_POINTS = frozenset({{
            "covered.point",
            "gap.point",{suppress}
        }})
        """)
    _write(root, "flyimg_tpu/service/app.py", """\
        from flyimg_tpu.testing import faults

        def make_app():
            faults.fire("covered.point")
            faults.fire("gap.point")
        """)
    _write(root, "tools/smoke_chaos.py", f"""\
        CAMPAIGN_POINTS = {campaign!r}
        """)
    return _scan(
        root, paths=("flyimg_tpu", "tools"), checkers=[RegistryChecker()]
    )


def test_chaos_coverage_gap_and_stale_entry_trip(tmp_path):
    """A KNOWN_POINTS entry missing from CAMPAIGN_POINTS is a coverage
    gap (the end-to-end no-failed-requests proof stopped applying to
    it); a CAMPAIGN_POINTS entry that KNOWN_POINTS never declared is a
    stale matrix cell that fires nothing."""
    result = _chaos_fixture(
        tmp_path, ("covered.point", "ghost.point")
    )
    by_rule = {}
    for f in result.findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert [f.message for f in by_rule["chaos-uncovered"]]
    assert "gap.point" in by_rule["chaos-uncovered"][0].message
    # anchored at the KNOWN_POINTS declaration, not the campaign matrix,
    # so the fingerprint survives matrix reordering
    assert by_rule["chaos-uncovered"][0].path == "flyimg_tpu/testing/faults.py"
    assert by_rule["chaos-uncovered"][0].symbol == "KNOWN_POINTS"
    assert "ghost.point" in by_rule["chaos-point-unknown"][0].message
    assert by_rule["chaos-point-unknown"][0].path == "tools/smoke_chaos.py"


def test_chaos_coverage_full_matrix_passes(tmp_path):
    result = _chaos_fixture(tmp_path, ("covered.point", "gap.point"))
    assert not [
        f for f in result.findings if f.rule.startswith("chaos-")
    ]


def test_chaos_coverage_suppression(tmp_path):
    result = _chaos_fixture(
        tmp_path, ("covered.point",),
        suppress="  # flylint: disable=chaos-uncovered",
    )
    assert not [
        f for f in result.findings if f.rule.startswith("chaos-")
    ]
    assert result.suppressed == 1


def test_chaos_coverage_absent_campaign_is_inert(tmp_path):
    """Fixture projects without a tools/smoke_chaos.py (every other
    checker test) must not trip chaos rules — the parity check needs
    BOTH registries present."""
    _write(tmp_path, "flyimg_tpu/testing/faults.py", """\
        KNOWN_POINTS = frozenset({"gap.point"})
        """)
    _write(tmp_path, "flyimg_tpu/service/app.py", """\
        from flyimg_tpu.testing import faults

        def make_app():
            faults.fire("gap.point")
        """)
    result = _scan(
        tmp_path, paths=("flyimg_tpu",), checkers=[RegistryChecker()]
    )
    assert not [
        f for f in result.findings if f.rule.startswith("chaos-")
    ]


# ---------------------------------------------------------------------------
# jax hazards checker


def _jax(root, body, relpath="flyimg_tpu/ops/mod.py"):
    _write(root, relpath, body)
    return _scan(root, checkers=[JaxHazardsChecker()])


def test_uncached_jit_trips_and_cached_passes(tmp_path):
    result = _jax(tmp_path, """\
        from functools import lru_cache
        import jax

        def per_call(x):
            return jax.jit(lambda v: v + 1)(x)

        @lru_cache(maxsize=8)
        def builder(shape):
            return jax.jit(lambda v: v + 1)

        TOP_LEVEL = jax.jit(lambda v: v * 2)
        """)
    (f,) = result.findings
    assert f.rule == "jax-uncached-jit" and f.symbol == "per_call"


def test_host_sync_in_jit_trips(tmp_path):
    result = _jax(tmp_path, """\
        import jax
        import numpy as np

        @jax.jit
        def bad(x):
            host = np.asarray(x)
            return x.item()

        def host_code(x):
            return np.asarray(x)  # not jitted: fine
        """)
    assert len(result.findings) == 2
    assert _rules(result) == {"jax-host-sync-in-jit"}


def test_traced_control_flow_trips_and_static_exempt(tmp_path):
    result = _jax(tmp_path, """\
        from functools import partial
        import jax

        @jax.jit
        def bad(x):
            if x > 0:
                return x
            return -x

        @partial(jax.jit, static_argnames=("mode",))
        def good(x, mode):
            if mode:   # static: resolved at trace time, fine
                return x
            return -x
        """)
    (f,) = result.findings
    assert f.rule == "jax-traced-control-flow" and f.symbol == "bad"


def test_jax_scope_limited_to_device_packages(tmp_path):
    result = _jax(tmp_path, """\
        import jax

        def per_call(x):
            return jax.jit(lambda v: v + 1)(x)
        """, relpath="flyimg_tpu/service/mod.py")
    assert not result.findings


# ---------------------------------------------------------------------------
# observability checker


def _obs(root, body, relpath="flyimg_tpu/service/mod.py"):
    _write(root, relpath, body)
    return _scan(root, checkers=[ObservabilityChecker()])


def test_span_unpaired_trips_and_with_passes(tmp_path):
    result = _obs(tmp_path, """\
        from flyimg_tpu.runtime import tracing

        def bad():
            tracing.span("fetch")

        def good():
            with tracing.span("fetch"):
                pass
        """)
    (f,) = result.findings
    assert f.rule == "span-unpaired" and f.symbol == "bad"


def test_span_direct_construction_outside_runtime_trips(tmp_path):
    body = """\
        from flyimg_tpu.runtime import tracing

        def bad():
            s = tracing.Span("x")
            s.end()
        """
    result = _obs(tmp_path / "outside", body)
    (f,) = result.findings
    assert f.rule == "span-direct-construction"
    # the same code in runtime/ is the sanctioned shared-span pattern
    result = _obs(
        tmp_path / "inside", body, relpath="flyimg_tpu/runtime/mod.py"
    )
    assert not result.findings


def test_span_unended_trips_escape_passes(tmp_path):
    result = _obs(tmp_path, """\
        from flyimg_tpu.runtime import tracing

        def bad():
            s = tracing.Span("x")
            s.set_attribute("k", 1)

        def ends():
            s = tracing.Span("x")
            s.end()

        def escapes():
            s = tracing.Span("x")
            return s

        def passes_on(sink):
            s = tracing.Span("x")
            sink.attach(s)
        """, relpath="flyimg_tpu/runtime/mod.py")
    (f,) = result.findings
    assert f.rule == "span-unended" and f.symbol == "bad"


# ---------------------------------------------------------------------------
# baseline + CLI + repo self-check


def test_baseline_round_trip(tmp_path):
    _conc_file = _write(tmp_path, "flyimg_tpu/mod.py", textwrap.dedent("""\
        import time

        class C:
            def work(self):
                with self._lock:
                    time.sleep(1)
        """))
    result = _scan(tmp_path, checkers=[ConcurrencyChecker()])
    assert len(result.findings) == 1 and result.new == result.findings
    baseline_path = str(tmp_path / "baseline.json")
    write_baseline(baseline_path, result.findings)
    baseline = load_baseline(baseline_path)
    assert len(baseline) == 1
    # accepted: same scan is clean
    result2 = _scan(
        tmp_path, checkers=[ConcurrencyChecker()], baseline=baseline
    )
    assert not result2.new and len(result2.baselined) == 1
    # justifications survive --update-baseline round trips
    fp = next(iter(baseline))
    baseline[fp]["justification"] = "accepted for the round-trip test"
    with open(baseline_path, "w", encoding="utf-8") as fh:
        json.dump({"version": 1, "entries": list(baseline.values())}, fh)
    write_baseline(
        baseline_path, result.findings, load_baseline(baseline_path)
    )
    assert load_baseline(baseline_path)[fp]["justification"] == (
        "accepted for the round-trip test"
    )
    # fixing the finding leaves the entry stale (reported, not fatal)
    with open(_conc_file, "w", encoding="utf-8") as fh:
        fh.write("x = 1\n")
    result3 = _scan(
        tmp_path, checkers=[ConcurrencyChecker()],
        baseline=load_baseline(baseline_path),
    )
    assert not result3.findings and len(result3.stale_baseline) == 1


def _run_cli(root, *args):
    return subprocess.run(
        [sys.executable, "-m", "tools.flylint", "--root", str(root), *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )


def test_cli_exit_codes(tmp_path):
    _write(tmp_path, "flyimg_tpu/mod.py", """\
        import time

        class C:
            def work(self):
                with self._lock:
                    time.sleep(1)
        """)
    trip = _run_cli(tmp_path, "--check")
    assert trip.returncode == 1, trip.stdout + trip.stderr
    assert "lock-held-blocking-call" in trip.stdout
    machine = _run_cli(tmp_path, "--check", "--json")
    assert machine.returncode == 1
    doc = json.loads(machine.stdout)
    assert doc["findings"][0]["rule"] == "lock-held-blocking-call"
    _write(tmp_path, "flyimg_tpu/mod.py", "x = 1\n")
    clean = _run_cli(tmp_path, "--check")
    assert clean.returncode == 0, clean.stdout + clean.stderr


def test_every_rule_has_description_and_owner():
    assert len(ALL_RULES) >= 15
    for rule, desc in ALL_RULES.items():
        assert rule and desc


def test_repo_scans_clean():
    """THE drift gate, enforced from inside tier-1: the real repo must
    have no findings beyond the committed, justified baseline. If this
    fails, either fix the finding or baseline it with a justification
    (docs/static-analysis.md)."""
    project = Project(REPO_ROOT, ["flyimg_tpu", "tools"])
    baseline = load_baseline(
        os.path.join(REPO_ROOT, "tools", "flylint", "baseline.json")
    )
    result = run_checkers(project, ALL_CHECKERS, baseline)
    assert not result.new, "\n".join(f.format() for f in result.new)
    # every accepted baseline entry must carry a written justification
    for entry in baseline.values():
        assert str(entry.get("justification", "")).strip(), entry


# ---------------------------------------------------------------------------
# lock-order witness


def test_witness_reports_seeded_ab_ba_cycle_with_both_stacks():
    """The seeded-deadlock self-test: two sites acquired A->B on one
    path and B->A on another must produce a cycle report carrying BOTH
    acquisition stacks (scoped witness — the session-wide graph never
    sees these locks)."""
    w = LockOrderWitness()
    lock_a = w.wrap_lock("seed/alpha.py:10")
    lock_b = w.wrap_lock("seed/beta.py:20")

    def path_one():
        with lock_a:
            with lock_b:
                pass

    def path_two():
        with lock_b:
            with lock_a:
                pass

    t1 = threading.Thread(target=path_one, name="seed-1")
    t1.start(); t1.join()
    t2 = threading.Thread(target=path_two, name="seed-2")
    t2.start(); t2.join()

    report = w.report()
    assert report is not None
    assert "lock-order cycle" in report
    assert "seed/alpha.py:10" in report and "seed/beta.py:20" in report
    # both edges, each with its acquisition stack (the function names of
    # both conflicting paths must be visible, TSan-style)
    assert "path_one" in report and "path_two" in report
    assert report.count("edge ") == 2


def test_witness_consistent_order_is_clean():
    w = LockOrderWitness()
    lock_a = w.wrap_lock("seed/a.py:1")
    lock_b = w.wrap_lock("seed/b.py:2")
    for _ in range(3):
        with lock_a:
            with lock_b:
                pass
    assert w.report() is None
    assert w.edge_count() == 1


def test_witness_same_site_instances_not_an_edge():
    """Two instances born at ONE site (per-request objects) acquired in
    sequence are instance churn, not an ordering contract."""
    w = LockOrderWitness()
    lock_1 = w.wrap_lock("seed/obj.py:5")
    lock_2 = w.wrap_lock("seed/obj.py:5")
    with lock_1:
        with lock_2:
            pass
    with lock_2:
        with lock_1:
            pass
    assert w.edge_count() == 0 and w.report() is None


def test_witness_rlock_reentrancy_and_condition_wait():
    """Reentrant acquires are one held entry (no self-edges); a
    Condition.wait fully releases the held lock so the witness must not
    blame the waiting thread for locks taken by the notifier."""
    import threading as th
    w = LockOrderWitness()
    orig = (th.Lock, th.RLock)
    th.Lock, th.RLock = w.make_lock, w.make_rlock
    try:
        cond = th.Condition()
        other = w.wrap_lock("seed/other.py:1")
        results = []

        def waiter():
            with cond:
                results.append(cond.wait(timeout=5))

        t = th.Thread(target=waiter)
        t.start()
        time.sleep(0.2)
        with other:       # if wait() leaked a held entry, this thread's
            with cond:    # cond acquisition under `other` is fine — but
                cond.notify_all()  # the WAITER re-acquiring after wake
        t.join()          # must not see `other` as held
        assert results == [True]
        assert w.report() is None
    finally:
        th.Lock, th.RLock = orig


def test_witness_pytest_plugin_fails_session_on_cycle(tmp_path):
    """End to end: a pytest session with the witness armed and a seeded
    AB/BA test must FAIL (exit status 3) with the cycle report, even
    though every test passed — the deadlock never has to happen to be
    caught."""
    _write(tmp_path, "conftest.py", f"""\
        import os, sys
        sys.path.insert(0, {REPO_ROOT!r})
        from tools.flylint.witness import install, session_report
        install(root=os.path.dirname(os.path.abspath(__file__)))

        def pytest_sessionfinish(session, exitstatus):
            report = session_report()
            if report:
                print(report)
                session.exitstatus = 3
        """)
    _write(tmp_path, "test_seeded_deadlock.py", """\
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def test_path_one():
            with A:
                with B:
                    pass

        def test_path_two():
            with B:
                with A:
                    pass
        """)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q",
         "-p", "no:cacheprovider"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 3, proc.stdout + proc.stderr
    assert "lock-order cycle" in proc.stdout
    assert "test_seeded_deadlock.py" in proc.stdout
    assert "2 passed" in proc.stdout  # no test failed — the GRAPH did


# ---------------------------------------------------------------------------
# regression tests for the real findings this PR fixed


def _aux_runner(payloads):
    return [p * 2 for p in payloads]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_batcher_heal_starts_executor_outside_lock(monkeypatch):
    """flylint lock-held-blocking-call @ batcher._maybe_heal_executor_
    locked: the healed executor thread must start AFTER the submission
    lock is released (Thread.start blocks; under the lock it convoys
    every submitter)."""
    from flyimg_tpu.runtime.batcher import BatchController
    from flyimg_tpu.runtime.metrics import MetricsRegistry
    from flyimg_tpu.testing import faults

    ctl = BatchController(
        max_batch=2, deadline_ms=1.0, lone_flush=True,
        metrics=MetricsRegistry(),
    )
    seen = {}
    orig_start = threading.Thread.start

    def checking_start(thread):
        if thread.name == "flyimg-batcher":
            seen["lock_owned_at_start"] = ctl._lock._is_owned()
        return orig_start(thread)

    try:
        faults.install(faults.FaultInjector()).plan(
            "batcher.execute",
            lambda **_: (_ for _ in ()).throw(SystemExit("chaos")),
        )
        fut = ctl.submit_aux(("k",), 21, _aux_runner)
        with pytest.raises(RuntimeError, match="executor died"):
            fut.result(timeout=60)
        for _ in range(500):
            if not ctl._thread.is_alive():
                break
            time.sleep(0.01)
        assert not ctl._thread.is_alive()
        faults.clear()
        monkeypatch.setattr(threading.Thread, "start", checking_start)
        fut = ctl.submit_aux(("k",), 21, _aux_runner)
        assert fut.result(timeout=60) == 42
        assert seen == {"lock_owned_at_start": False}
        assert ctl.metrics.summary()[
            'flyimg_executor_restarts_total{reason="dead"}'
        ] == 1
    finally:
        monkeypatch.undo()
        faults.clear()
        ctl.close(drain_timeout_s=5.0)


def test_refresh_queue_spawns_worker_outside_lock(monkeypatch):
    """flylint lock-held-blocking-call @ brownout.RefreshQueue.submit:
    the lazily-started refresh worker must start outside the queue lock
    — and exactly one worker spawns for N submissions."""
    from flyimg_tpu.runtime.brownout import RefreshQueue

    rq = RefreshQueue(max_pending=8)
    seen = []
    orig_start = threading.Thread.start

    def checking_start(thread):
        if thread.name == "flyimg-swr-refresh":
            seen.append(rq._lock.locked())
        return orig_start(thread)

    monkeypatch.setattr(threading.Thread, "start", checking_start)
    done = threading.Event()
    ran = []

    def job(tag):
        def fn():
            ran.append(tag)
            if len(ran) >= 3:
                done.set()
        return fn

    assert rq.submit("a", job("a"))
    assert rq.submit("b", job("b"))
    assert rq.submit("c", job("c"))
    assert done.wait(timeout=30)
    assert seen == [False]  # one spawn, lock released at start time
    assert sorted(ran) == ["a", "b", "c"]


def test_missing_params_exception_maps_to_500():
    """flylint exception-unmapped: MissingParamsException now has an
    explicit _ERROR_STATUS entry (and stays a 500 — our fault, not the
    caller's)."""
    from flyimg_tpu.exceptions import MissingParamsException
    from flyimg_tpu.service.app import _ERROR_STATUS, _error_response

    assert _ERROR_STATUS[MissingParamsException] == 500
    resp = _error_response(MissingParamsException("security_key unset"))
    assert resp.status == 500
    assert "MissingParamsException" in resp.text


def test_appconfig_declares_every_consumed_knob():
    """flylint knob-undeclared/knob-unread: the knobs this PR surfaced
    as drift are now declared (and the dead `device_mesh` is gone)."""
    from flyimg_tpu.appconfig import SERVER_DEFAULTS

    for knob in (
        "decode_batch_max", "decode_deadline_ms", "face_backend",
        "face_checkpoint", "compilation_cache_dir",
        "cache_max_bytes",
        "cache_prune_interval_s", "routes", "gcs", "fault_injector",
        "brownout_clock", "application_name",
    ):
        assert knob in SERVER_DEFAULTS, knob
    # knobs that went with the code that read them
    assert "device_mesh" not in SERVER_DEFAULTS
    assert "backend_probe_timeout_s" not in SERVER_DEFAULTS


def test_healthz_reports_application_name(tmp_path):
    """flylint knob-unread: `application_name` is now wired into
    /healthz so the declared knob does something observable."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from flyimg_tpu.appconfig import AppParameters
    from flyimg_tpu.service.app import make_app

    async def go():
        app = make_app(AppParameters({
            "application_name": "flyimg-test-fleet",
            "tmp_dir": str(tmp_path / "tmp"),
            "upload_dir": str(tmp_path / "uploads"),
        }))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.get("/healthz")
            return resp.status, await resp.json()
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        status, body = loop.run_until_complete(go())
    finally:
        loop.close()
    assert status == 200
    assert body["app"] == "flyimg-test-fleet"


def test_fault_point_registry_matches_module_docstring():
    """KNOWN_POINTS is the machine half of the faults.py contract; the
    prose half (the module docstring) must name every declared point."""
    from flyimg_tpu.testing import faults

    for point in faults.KNOWN_POINTS:
        assert point in (faults.__doc__ or ""), point
