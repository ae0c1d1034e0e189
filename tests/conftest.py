"""Test harness config: force the CPU backend with a virtual 8-device mesh
so sharding tests run anywhere (the standard fake-mesh trick; see SURVEY.md
section 4). The recipe lives in one place —
``flyimg_tpu.parallel.mesh.force_cpu_platform`` — shared with the driver
contract (``__graft_entry__.dryrun_multichip``); it also sets the explicit
``JAX_PLATFORMS=cpu`` pin without which ``make_app`` refuses a CPU backend.

Opt-in lock-order witness (docs/static-analysis.md "Lock-order witness"):
``FLYIMG_LOCK_WITNESS=1`` arms ``tools.flylint.witness`` BEFORE any
flyimg_tpu import below constructs a lock, builds the global lock-order
graph across the whole run, and fails the session (exit status 3) when
the graph contains a cycle — a latent AB/BA deadlock, reported with both
acquisition stacks even if no test ever actually hung.

Opt-in retrace sentinel (docs/static-analysis.md "Retrace sentinel"):
``FLYIMG_RETRACE_SENTINEL=1`` arms ``tools.flylint.retrace_sentinel``
AFTER the CPU platform is forced (it imports ``ops.compose``), counts
distinct XLA compiles per program-key family across the whole run, and
fails the session (exit status 4) when one family exceeds the compile
budget — a compile storm, reported with the varying key component named
and the first/breaching compile stacks.
"""

import os as _os
import sys as _sys

_LOCK_WITNESS = _os.environ.get("FLYIMG_LOCK_WITNESS") == "1"
if _LOCK_WITNESS:
    from tools.flylint.witness import install as _witness_install

    _witness_install()

from flyimg_tpu.parallel.mesh import force_cpu_platform  # noqa: E402

force_cpu_platform(8)

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", jax.devices()
assert len(jax.devices()) == 8, jax.devices()

_RETRACE_SENTINEL = _os.environ.get("FLYIMG_RETRACE_SENTINEL") == "1"
if _RETRACE_SENTINEL:
    from tools.flylint.retrace_sentinel import install as _sentinel_install

    _sentinel_install()


def pytest_sessionfinish(session, exitstatus):
    """Whole-session verdicts from the armed runtime monitors. The
    lock-order witness reports an acquisition-order cycle (exit status
    3); the retrace sentinel reports a compile storm with its varying
    key component (exit status 4). Reports land on stderr first."""
    if _LOCK_WITNESS:
        from tools.flylint.witness import installed_witness, session_report

        report = session_report()
        witness = installed_witness()
        if witness is not None:
            print(
                f"\nflylint lock-order witness: {witness.tracked_locks} "
                f"tracked lock(s), {witness.edge_count()} order edge(s), "
                f"cycle={'YES' if report else 'no'}",
                file=_sys.stderr,
            )
        if report:
            print(report, file=_sys.stderr)
            session.exitstatus = 3
    if _RETRACE_SENTINEL:
        from tools.flylint.retrace_sentinel import (
            installed_sentinel,
            session_report as _sentinel_report,
        )

        report = _sentinel_report()
        sentinel = installed_sentinel()
        if sentinel is not None:
            worst, component = sentinel.max_family()
            print(
                f"\nflylint retrace sentinel: {sentinel.compiles} "
                f"compile(s), {sentinel.family_count()} key famil(ies), "
                f"worst family {worst} distinct"
                + (f" (`{component}`)" if component else "")
                + f" / budget {sentinel.budget}, "
                f"storm={'YES' if report else 'no'}",
                file=_sys.stderr,
            )
        if report:
            print(report, file=_sys.stderr)
            session.exitstatus = 4
