"""Runtime retrace sentinel: compile-storm detection with attribution.

The static ``program-identity`` checkers prove the cache keys are
*complete* (every traced value is keyed); they cannot prove the keys are
*bounded* — that no per-request value reaches a key component without
passing a bucketing helper. An unbucketed component compiles one XLA
program per distinct request: the serving path serializes behind the
compiler, the program cache churns, and nothing errors ("Beyond
Inference", arXiv 2403.12981 — the host-side pathology that dominates CV
serving). This module is the dynamic half of that proof, mirroring the
lock witness (``witness.py``): it hooks the one place every device
program is born — ``ops/compose.ProgramHandle`` — and counts distinct
compiles per key *family*.

A family is a program key with ONE component masked out: the key layouts
are known (``("single", in_shape, resample_out, pad_canvas, pad_offset,
plan, band_taps, depth)`` and the ``"batched"`` eleven-tuple), so every compile
feeds len(key) families — "all components fixed except ``in_shape``",
"all fixed except ``band_taps``", … A compile storm driven by one
unbucketed value lands every compile in the SAME family, whose distinct-
value count then crosses the budget; the varying component is therefore
*named* in the report, not inferred. Legitimate variant growth (many
plans, a few shape buckets per plan) spreads across families and stays
far under budget — bucketed dims contribute O(log size) values.

Opt-in: ``FLYIMG_RETRACE_SENTINEL=1`` makes ``tests/conftest.py`` call
:func:`install` (after the CPU platform is forced, before any program
compiles) and fail the pytest session with exit status **4** — distinct
from the lock witness's 3 — when :func:`session_report` finds a breached
family, TSan-style: first and breaching compile stacks plus the fixed
key template. Budget: ``FLYIMG_RETRACE_BUDGET`` (default
:data:`DEFAULT_BUDGET` distinct compiles per family).

Scoped self-tests build a private :class:`RetraceSentinel` and feed keys
by hand; the e2e test seeds a real storm inside a subprocess pytest
session (``tests/test_retrace_sentinel.py``).
"""

from __future__ import annotations

import os
import sys
import threading
import traceback
from typing import Dict, List, Optional, Tuple

__all__ = [
    "RetraceSentinel",
    "DEFAULT_BUDGET",
    "install",
    "uninstall",
    "installed_sentinel",
    "session_report",
]

DEFAULT_BUDGET = 24

#: key-tuple component names by kind tag (must mirror the ``key =``
#: tuples in ``ops/compose.build_program`` and
#: ``runtime/batcher.build_batched_program`` — the static
#: ``program-key-drift`` rule keeps those from growing silently, and
#: ``tests/test_retrace_sentinel.py`` pins this map against the real
#: keys so a new component cannot desynchronize it)
COMPONENT_NAMES: Dict[str, Tuple[str, ...]] = {
    "single": (
        "kind", "in_shape", "resample_out", "pad_canvas", "pad_offset",
        "plan", "band_taps", "depth",
    ),
    "batched": (
        "kind", "batch_size", "in_shape", "resample_out", "pad_canvas",
        "pad_offset", "plan", "rotate_dynamic", "mesh", "band_taps", "depth",
        "word_size",
    ),
}


class _Hole:
    """Placeholder for the masked component in a family key."""

    _instance: Optional["_Hole"] = None

    def __new__(cls) -> "_Hole":
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "<varies>"


_HOLE = _Hole()


def _component_names(key: tuple) -> Tuple[str, ...]:
    names = COMPONENT_NAMES.get(key[0] if key else None)
    if names is not None and len(names) == len(key):
        return names
    return tuple(f"component[{i}]" for i in range(len(key)))


def _short(value: object, limit: int = 96) -> str:
    text = repr(value)
    return text if len(text) <= limit else text[: limit - 3] + "..."


class _Family:
    """One (masked-component, fixed-rest) bucket: the distinct values the
    masked slot has taken, with first/latest stacks for the report."""

    __slots__ = (
        "kind", "component", "fixed", "values", "first_value",
        "first_stack", "latest_value", "latest_stack", "breach_value",
        "breach_stack",
    )

    def __init__(self, kind: str, component: str, fixed: tuple) -> None:
        self.kind = kind
        self.component = component
        self.fixed = fixed  # the key with _HOLE at the masked slot
        self.values: Dict[str, int] = {}  # value repr -> compile count
        self.first_value: Optional[str] = None
        self.first_stack: Optional[str] = None
        self.latest_value: Optional[str] = None
        self.latest_stack: Optional[str] = None
        # frozen at the moment the budget is crossed (later compiles
        # keep updating latest_* but never these)
        self.breach_value: Optional[str] = None
        self.breach_stack: Optional[str] = None

    def note(self, value: object, stack: str) -> int:
        rendered = repr(value)
        fresh = rendered not in self.values
        self.values[rendered] = self.values.get(rendered, 0) + 1
        if self.first_stack is None:
            self.first_value = rendered
            self.first_stack = stack
        if fresh:
            self.latest_value = rendered
            self.latest_stack = stack
        return len(self.values)


class RetraceSentinel:
    """Per-family distinct-compile counter. One global instance is armed
    by :func:`install`; tests may build private ones and call
    :meth:`note_compile` directly."""

    def __init__(self, budget: Optional[int] = None) -> None:
        if budget is None:
            # a garbage env seed falls back to the default instead of
            # erroring the whole armed session at conftest import time
            # (same hardening contract as FLYIMG_RESAMPLE_KERNEL)
            try:
                budget = int(
                    os.environ.get(
                        "FLYIMG_RETRACE_BUDGET", str(DEFAULT_BUDGET)
                    )
                )
            except ValueError:
                budget = DEFAULT_BUDGET
        self.budget = budget
        self._lock = threading.Lock()
        self._families: Dict[tuple, _Family] = {}
        # id(handle) -> structured key, filled by the patched __init__
        # (handles live in the builders' lru caches; a recycled id simply
        # overwrites its stale entry)
        self._handle_keys: Dict[int, tuple] = {}
        self.compiles = 0
        self._breached: Optional[_Family] = None

    # -- hook plumbing -----------------------------------------------------

    def note_handle(self, handle: object, key: object) -> None:
        if isinstance(key, tuple) and key and isinstance(key[0], str):
            self._handle_keys[id(handle)] = key

    def note_handle_compile(self, handle: object) -> None:
        key = self._handle_keys.get(id(handle))
        if key is not None:
            self.note_compile(key)

    # -- event stream ------------------------------------------------------

    def note_compile(self, key: tuple) -> None:
        """One program compile for ``key``: feeds every one-hole family
        the key belongs to."""
        stack = "".join(traceback.format_stack(sys._getframe(1)))
        names = _component_names(key)
        with self._lock:
            self.compiles += 1
            for i, name in enumerate(names):
                if name == "kind":
                    continue  # the literal tag never varies per request
                fixed = key[:i] + (_HOLE,) + key[i + 1:]
                family = self._families.get(fixed)
                if family is None:
                    family = _Family(str(key[0]), name, fixed)
                    self._families[fixed] = family
                distinct = family.note(key[i], stack)
                if distinct > self.budget and family.breach_value is None:
                    # freeze the breach attribution NOW: later fresh
                    # values keep advancing latest_* but the report must
                    # show the compile that actually crossed the budget
                    family.breach_value = family.latest_value
                    family.breach_stack = family.latest_stack
                    if self._breached is None:
                        self._breached = family

    # -- analysis ----------------------------------------------------------

    def family_count(self) -> int:
        return len(self._families)

    def max_family(self) -> Tuple[int, Optional[str]]:
        """(largest distinct-value count, its component name)."""
        best, name = 0, None
        for family in self._families.values():
            if len(family.values) > best:
                best, name = len(family.values), family.component
        return best, name

    def breached(self) -> Optional[_Family]:
        return self._breached

    def report(self) -> Optional[str]:
        """Human-readable TSan-style storm report, or None when every
        family stayed within budget."""
        family = self._breached
        if family is None:
            return None
        names = _component_names(family.fixed)
        fixed_parts = [
            f"{name}={_short(value)}"
            for name, value in zip(names, family.fixed)
            if not isinstance(value, _Hole)
        ]
        values = sorted(family.values)
        shown = ", ".join(_short(v, 48) for v in values[:8])
        if len(values) > 8:
            shown += f", ... ({len(values) - 8} more)"
        lines = [
            "retrace compile storm detected by the flylint sentinel "
            "(tools/flylint/retrace_sentinel.py):",
            f"  one key family compiled {len(family.values)} distinct "
            f"programs (budget {self.budget}) with every other "
            "program-identity component fixed.",
            f"  varying component: `{family.component}` "
            f"(kind={family.kind!r})",
            "  fixed components: " + " ".join(fixed_parts),
            f"  distinct `{family.component}` values: {shown}",
            "",
        ]
        if family.first_stack:
            lines.append(
                f"first compile in this family ({family.component}="
                f"{_short(family.first_value, 48)}):"
            )
            lines.append(family.first_stack.rstrip("\n"))
            lines.append("")
        if family.breach_stack and family.breach_stack is not family.first_stack:
            lines.append(
                f"budget-breaching compile ({family.component}="
                f"{_short(family.breach_value, 48)}):"
            )
            lines.append(family.breach_stack.rstrip("\n"))
            lines.append("")
        lines.append(
            f"Fix: `{family.component}` is reaching program identity "
            "unbucketed — route it through a bucketing helper "
            "(_bucket_dim / bucket_taps / select_band_taps) or raise "
            "FLYIMG_RETRACE_BUDGET if the variants are intended; see "
            "docs/static-analysis.md 'Retrace sentinel'."
        )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# global installation

_INSTALLED: Optional[RetraceSentinel] = None
_REAL_INIT = None
_REAL_COMPILE = None


def install(budget: Optional[int] = None) -> RetraceSentinel:
    """Arm the sentinel process-wide: ``ProgramHandle`` construction and
    compilation report into one global instance. Idempotent. Imports
    ``ops.compose`` — in pytest, tests/conftest.py calls this AFTER the
    CPU platform is forced and before any program compiles."""
    global _INSTALLED, _REAL_INIT, _REAL_COMPILE
    if _INSTALLED is not None:
        return _INSTALLED
    from flyimg_tpu.ops.compose import ProgramHandle

    sentinel = RetraceSentinel(budget)
    _REAL_INIT = ProgramHandle.__init__
    _REAL_COMPILE = ProgramHandle._compile

    def __init__(self, jitted, key, descriptor, **kwargs):  # noqa: N807
        _REAL_INIT(self, jitted, key, descriptor, **kwargs)
        sentinel.note_handle(self, key)

    def _compile(self, args):
        sentinel.note_handle_compile(self)
        return _REAL_COMPILE(self, args)

    ProgramHandle.__init__ = __init__
    ProgramHandle._compile = _compile
    _INSTALLED = sentinel
    return sentinel


def uninstall() -> None:
    """Restore the real ``ProgramHandle`` methods."""
    global _INSTALLED
    if _INSTALLED is None:
        return
    from flyimg_tpu.ops.compose import ProgramHandle

    ProgramHandle.__init__ = _REAL_INIT
    ProgramHandle._compile = _REAL_COMPILE
    _INSTALLED = None


def installed_sentinel() -> Optional[RetraceSentinel]:
    return _INSTALLED


def session_report() -> Optional[str]:
    """The installed sentinel's storm report (None = not armed, or no
    family over budget)."""
    if _INSTALLED is None:
        return None
    return _INSTALLED.report()
