"""Retrace sentinel suite (tools/flylint/retrace_sentinel.py,
docs/static-analysis.md "Retrace sentinel").

Layers:

1. **Scoped self-tests** — a private :class:`RetraceSentinel` fed keys
   by hand: one varying component breaches its family and the report
   names it (with the fixed key template and both stacks); legitimate
   variant growth spread across components stays clean; unknown key
   layouts degrade to positional names without crashing.
2. **Key-map parity pin** — the sentinel's ``COMPONENT_NAMES`` table
   must mirror the REAL ``key = (...)`` tuples in
   ``ops/compose.build_program`` and
   ``runtime/batcher.build_batched_program``: real compiles must land in
   families with *named* components (a new key component that is not
   added to the map would surface here as ``component[i]``).
3. **End-to-end** — a subprocess pytest session with the sentinel armed
   and a seeded per-request-varying static arg FAILS with exit status 4
   (distinct from the lock witness's 3) and the varying component named
   in the storm report, even though every test passed; the bucketed
   equivalent passes.
"""

import os
import subprocess
import sys
import textwrap

from tools.flylint.retrace_sentinel import (
    COMPONENT_NAMES,
    DEFAULT_BUDGET,
    RetraceSentinel,
    install,
    installed_sentinel,
    uninstall,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _single_key(in_shape=(128, 128), resample_out=(64, 64),
                pad_canvas=None, pad_offset=(0, 0), plan="planA",
                band_taps=None, depth=8):
    return ("single", in_shape, resample_out, pad_canvas, pad_offset,
            plan, band_taps, depth)


# ---------------------------------------------------------------------------
# scoped self-tests


def test_storm_breaches_family_and_names_component():
    """Six distinct in_shape values with every other component fixed:
    the in_shape family crosses a budget of 4 and the report attributes
    the storm to it."""
    s = RetraceSentinel(budget=4)
    for h in range(100, 106):
        s.note_compile(_single_key(in_shape=(h, 128)))
    assert s.compiles == 6
    worst, component = s.max_family()
    assert (worst, component) == (6, "in_shape")
    breached = s.breached()
    assert breached is not None and breached.component == "in_shape"
    report = s.report()
    assert report is not None
    assert "varying component: `in_shape`" in report
    assert "6 distinct" in report and "budget 4" in report
    # the fixed key template names every OTHER component
    assert "plan='planA'" in report
    assert "band_taps=None" in report
    # first and breaching compile stacks, TSan-style
    assert "first compile in this family" in report
    assert "budget-breaching compile" in report
    assert "test_retrace_sentinel.py" in report
    assert "bucketing helper" in report  # the fix guidance


def test_spread_variants_stay_clean():
    """Legitimate growth — a few shape buckets per plan across a few
    plans — spreads across families and never breaches."""
    s = RetraceSentinel(budget=4)
    for plan in ("planA", "planB", "planC"):
        for shape in ((128, 128), (256, 256), (384, 384)):
            s.note_compile(_single_key(in_shape=shape, plan=plan))
    assert s.report() is None
    assert s.breached() is None
    worst, _component = s.max_family()
    assert worst == 3  # 3 shapes per fixed plan / 3 plans per fixed shape


def test_repeat_compiles_of_one_key_are_one_distinct_value():
    """Recompiling the SAME key (cache eviction, handle churn) never
    advances any family's distinct count."""
    s = RetraceSentinel(budget=2)
    for _ in range(10):
        s.note_compile(_single_key())
    assert s.compiles == 10
    worst, _ = s.max_family()
    assert worst == 1
    assert s.report() is None


def test_unknown_key_layout_degrades_to_positional_names():
    """A key kind the map does not know (e.g. the aux-runner keys) still
    counts — with positional component names, never a crash."""
    s = RetraceSentinel(budget=2)
    for i in range(4):
        s.note_compile(("aux", f"runner{i}", ("nested", "payload")))
    breached = s.breached()
    assert breached is not None
    assert breached.component == "component[1]"
    assert "component[1]" in s.report()


def test_budget_from_env(monkeypatch):
    monkeypatch.setenv("FLYIMG_RETRACE_BUDGET", "7")
    assert RetraceSentinel().budget == 7
    monkeypatch.delenv("FLYIMG_RETRACE_BUDGET")
    assert RetraceSentinel().budget == DEFAULT_BUDGET
    # a garbage seed falls back to the default instead of erroring the
    # armed session at conftest import time
    monkeypatch.setenv("FLYIMG_RETRACE_BUDGET", "24x")
    assert RetraceSentinel().budget == DEFAULT_BUDGET


def test_breach_attribution_is_frozen_at_the_crossing():
    """The report's two stacks must name the ACTUAL first compile (not
    the lexicographically smallest value) and the ACTUAL budget-crossing
    compile (not whatever fresh value arrived last before session
    end)."""
    s = RetraceSentinel(budget=2)
    # (9, 128) sorts AFTER (100, 128) lexicographically but compiles
    # first; (101, 128) crosses the budget; (102, 128) arrives later
    for h in (9, 100, 101, 102):
        s.note_compile(_single_key(in_shape=(h, 128)))
    family = s.breached()
    assert family is not None
    assert family.first_value == repr((9, 128))
    assert family.breach_value == repr((101, 128))
    assert family.latest_value == repr((102, 128))  # kept advancing
    report = s.report()
    assert "first compile in this family (in_shape='(9, 128)')" in report
    assert "budget-breaching compile (in_shape='(101, 128)')" in report


# ---------------------------------------------------------------------------
# key-map parity pin against the real builders


def test_component_names_match_real_program_keys():
    """Real single AND batched compiles must land in families with NAMED
    components — len(COMPONENT_NAMES[kind]) matching the real key tuple
    is exactly what makes that happen, so a key component added to
    compose/batcher without updating the sentinel map fails here."""
    import jax
    import jax.numpy as jnp

    from flyimg_tpu.ops import compose
    from flyimg_tpu.runtime.batcher import build_batched_program
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan

    pre_armed = installed_sentinel()
    sentinel = pre_armed if pre_armed is not None else install()
    try:
        # unusual geometry => fresh lru entries => real compiles observed
        plan = build_plan(OptionsBag("w_52,h_36"), 212, 148)
        layout = compose.plan_layout(plan)
        dp = plan.device_plan()
        in_shape = (148, 212)
        args = (
            jax.ShapeDtypeStruct((*in_shape, 3), jnp.uint8),
            *(jax.ShapeDtypeStruct((2,), jnp.float32) for _ in range(4)),
        )
        compose.build_program(
            in_shape, layout.resample_out, layout.pad_canvas,
            layout.pad_offset, dp, None,
        ).precompile(args)
        batched_args = tuple(
            jax.ShapeDtypeStruct((2, *a.shape), a.dtype) for a in args
        )
        build_batched_program(
            2, in_shape, layout.resample_out, layout.pad_canvas,
            layout.pad_offset, dp,
        ).precompile(batched_args)

        seen = {}
        for family in sentinel._families.values():
            seen.setdefault(family.kind, set()).add(family.component)
        for kind in ("single", "batched"):
            assert kind in seen, (
                f"no {kind} compile was observed — the sentinel hook "
                "on ProgramHandle is not seeing real programs"
            )
            expected = set(COMPONENT_NAMES[kind]) - {"kind"}
            assert seen[kind] == expected, (
                f"{kind} key layout drifted from COMPONENT_NAMES: "
                f"families {sorted(seen[kind])} vs map {sorted(expected)}"
                " — update tools/flylint/retrace_sentinel.py"
            )
            assert not any(c.startswith("component[") for c in seen[kind])
    finally:
        if pre_armed is None:
            uninstall()


# ---------------------------------------------------------------------------
# end-to-end subprocess sessions


def _write(root, relpath, text):
    path = os.path.join(str(root), relpath)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(textwrap.dedent(text))
    return path


_E2E_CONFTEST = f"""\
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {REPO_ROOT!r})
    from tools.flylint.retrace_sentinel import install, session_report

    install(budget=3)

    def pytest_sessionfinish(session, exitstatus):
        report = session_report()
        if report:
            print(report)
            session.exitstatus = 4
    """

_E2E_BODY = """\
    import jax
    import jax.numpy as jnp

    from flyimg_tpu.ops import compose
    from flyimg_tpu.spec.options import OptionsBag
    from flyimg_tpu.spec.plan import build_plan


    def _compile_at(in_shape):
        plan = build_plan(OptionsBag("w_16,h_12"), 64, 48)
        layout = compose.plan_layout(plan)
        fn = compose.build_program(
            in_shape, layout.resample_out, layout.pad_canvas,
            layout.pad_offset, plan.device_plan(), None,
        )
        fn.precompile((
            jax.ShapeDtypeStruct((*in_shape, 3), jnp.uint8),
            *(jax.ShapeDtypeStruct((2,), jnp.float32) for _ in range(4)),
        ))
"""


def _run_session(tmp_path, test_body):
    _write(tmp_path, "conftest.py", _E2E_CONFTEST)
    _write(tmp_path, "test_seeded.py",
           textwrap.dedent(_E2E_BODY) + textwrap.dedent(test_body))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLYIMG_RETRACE_SENTINEL", None)  # tmp conftest arms its own
    env.pop("FLYIMG_LOCK_WITNESS", None)
    return subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q",
         "-p", "no:cacheprovider"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=420,
        env=env,
    )


def test_sentinel_session_fails_on_seeded_storm(tmp_path):
    """A per-request-varying static arg (unbucketed in_shape) compiles
    one program per request: the session FAILS with exit status 4 and
    the storm report names `in_shape` — even though the test passed."""
    proc = _run_session(tmp_path, """\


        def test_per_request_shapes():
            # 6 distinct source sizes reach program identity unbucketed
            for i in range(6):
                _compile_at((40 + i, 64))
        """)
    assert proc.returncode == 4, proc.stdout + proc.stderr
    assert "retrace compile storm" in proc.stdout
    assert "varying component: `in_shape`" in proc.stdout
    assert "1 passed" in proc.stdout  # no test failed — the SENTINEL did


def test_sentinel_session_passes_when_bucketed(tmp_path):
    """The bucketed equivalent — every request landing in one shape
    bucket — compiles once and the armed session passes clean."""
    proc = _run_session(tmp_path, """\


        def test_bucketed_shapes():
            for _ in range(6):
                _compile_at((64, 64))   # one bucket -> one program
        """)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1 passed" in proc.stdout
