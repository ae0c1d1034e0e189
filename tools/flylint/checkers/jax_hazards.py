"""Host/device boundary hazards in the device-code packages.

Scope: ``flyimg_tpu/ops/``, ``flyimg_tpu/models/``,
``flyimg_tpu/parallel/`` — the modules whose functions run under
``jax.jit``. The hazard classes are the ones the TensorFlow paper (arXiv
1605.08695) and the accelerator guides call out for serving:

- **uncached jit** (``jax-uncached-jit``): ``jax.jit(...)`` invoked
  inside a function body builds a NEW jitted callable per call — every
  call retraces (and outside the persistent XLA cache, recompiles). The
  sanctioned pattern is a module-level jit or an ``lru_cache``d builder
  (ops/compose.build_program, parallel/tiling._build_*).
- **host sync in jit** (``jax-host-sync-in-jit``): ``.item()``,
  ``np.asarray``/``np.array``, or ``float()``/``int()`` on a traced
  parameter inside a jitted function blocks on device->host transfer at
  trace time (or fails under jit) — the launch pipeline stalls.
- **traced control flow** (``jax-traced-control-flow``): ``if``/``while``
  on a traced parameter inside a jitted function is data-dependent Python
  control flow — it either fails at trace time or silently bakes one
  branch into the compiled program. ``static_argnames``/``static_argnums``
  parameters are exempt.

Jit scope is resolved lexically: functions decorated with ``jax.jit`` /
``partial(jax.jit, ...)``, functions passed by name to a ``jax.jit(...)``
call in the same module, and defs nested inside those. Cross-module
jitting (a factory returning a closure that a caller jits) is out of
lexical reach — the runtime witness and parity tests cover that side.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from tools.flylint.core import Finding, Project, literal_str

RULE_UNCACHED_JIT = "jax-uncached-jit"
RULE_HOST_SYNC = "jax-host-sync-in-jit"
RULE_TRACED_FLOW = "jax-traced-control-flow"

SCOPE_PREFIXES = (
    "flyimg_tpu/ops/", "flyimg_tpu/models/", "flyimg_tpu/parallel/",
)

# ops/compose.py's ``bound_lru_cache`` is an lru_cache keyed on the bound
# arguments
_CACHE_DECORATORS = {"lru_cache", "cache", "bound_lru_cache"}


def _dotted(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute):
        return f"{_dotted(node.value)}.{node.attr}"
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)`` decorator or
    callee. A Call node only matches through the partial() decorator
    shape — ``jax.jit(f)(x)``'s OUTER call is an invocation of the
    jitted callable, not a second jit."""
    if isinstance(node, ast.Call):
        if _dotted(node.func) in ("partial", "functools.partial"):
            return bool(node.args) and _is_jit_expr(node.args[0])
        return False
    return _dotted(node) in ("jax.jit", "jit")


def _static_argnames(decorators: List[ast.expr]) -> Set[str]:
    names: Set[str] = set()
    for dec in decorators:
        if isinstance(dec, ast.Call) and _is_jit_expr(dec):
            for kw in dec.keywords:
                if kw.arg in ("static_argnames", "static_argnums"):
                    for elt in getattr(kw.value, "elts", [kw.value]):
                        s = literal_str(elt)
                        if s is not None:
                            names.add(s)
    return names


def _has_cache_decorator(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        name = _dotted(dec.func if isinstance(dec, ast.Call) else dec)
        if name.split(".")[-1] in _CACHE_DECORATORS:
            return True
    return False


class JaxHazardsChecker:
    name = "jax-hazards"
    rules = {
        RULE_UNCACHED_JIT: (
            "jax.jit(...) called inside an uncached function body "
            "(retraces/recompiles every call)"
        ),
        RULE_HOST_SYNC: (
            "a device->host sync (.item()/np.asarray/float/int on a "
            "traced value) inside a jitted function"
        ),
        RULE_TRACED_FLOW: (
            "Python if/while on a traced parameter inside a jitted "
            "function (data-dependent control flow)"
        ),
    }

    def run(self, project: Project) -> Iterable[Finding]:
        for src in project.files:
            if src.tree is None:
                continue
            if not any(src.relpath.startswith(p) for p in SCOPE_PREFIXES):
                continue
            yield from self._check_file(src)

    # ------------------------------------------------------------------

    def _check_file(self, src) -> Iterable[Finding]:
        jitted_names = self._names_passed_to_jit(src.tree)
        yield from self._walk(src, src.tree, symbol="", in_jit=False,
                              cached=False, in_function=False,
                              jitted_names=jitted_names)

    def _names_passed_to_jit(self, tree: ast.AST) -> Set[str]:
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and _is_jit_expr(node.func):
                if node.args and isinstance(node.args[0], ast.Name):
                    names.add(node.args[0].id)
        return names

    def _walk(self, src, node: ast.AST, symbol: str, in_jit: bool,
              cached: bool, in_function: bool,
              jitted_names: Set[str]) -> Iterable[Finding]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                child_symbol = (
                    f"{symbol}.{child.name}" if symbol else child.name
                )
                decorated_jit = any(
                    _is_jit_expr(d) for d in child.decorator_list
                )
                child_in_jit = (
                    in_jit or decorated_jit
                    or child.name in jitted_names
                )
                child_cached = cached or _has_cache_decorator(child)
                if child_in_jit:
                    statics = _static_argnames(child.decorator_list)
                    yield from self._check_jit_body(
                        src, child, child_symbol, statics
                    )
                yield from self._walk(
                    src, child, child_symbol, child_in_jit,
                    child_cached, True, jitted_names,
                )
            elif isinstance(child, ast.ClassDef):
                child_symbol = (
                    f"{symbol}.{child.name}" if symbol else child.name
                )
                yield from self._walk(
                    src, child, child_symbol, in_jit, cached,
                    in_function, jitted_names,
                )
            else:
                if (
                    in_function and not in_jit and not cached
                    and isinstance(child, ast.Call)
                    and _is_jit_expr(child.func)
                ):
                    # inside a plain function body: a jit() call here
                    # makes a fresh traced callable per invocation
                    yield Finding(
                        rule=RULE_UNCACHED_JIT,
                        path=src.relpath,
                        line=child.lineno,
                        symbol=symbol,
                        message=(
                            "jax.jit(...) inside an uncached function "
                            "body builds a new jitted callable every "
                            "call — hoist to module level or an "
                            "lru_cache'd builder"
                        ),
                    )
                yield from self._walk(
                    src, child, symbol, in_jit, cached, in_function,
                    jitted_names,
                )

    # ------------------------------------------------------------------

    def _check_jit_body(self, src, fn, symbol: str,
                        statics: Set[str]) -> Iterable[Finding]:
        params = {
            a.arg for a in (
                fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
            )
        } - statics - {"self"}

        def mentions_param(node: ast.AST) -> Optional[str]:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id in params:
                    return sub.id
            return None

        def own_nodes(root: ast.AST):
            """This function's own body, nested defs excluded (they are
            visited separately with their own parameter sets)."""
            for child in ast.iter_child_nodes(root):
                if isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                yield child
                yield from own_nodes(child)

        for node in own_nodes(fn):
            if isinstance(node, ast.Call):
                func = node.func
                name = _dotted(func)
                if isinstance(func, ast.Attribute) and func.attr == "item" \
                        and not node.args:
                    yield Finding(
                        rule=RULE_HOST_SYNC, path=src.relpath,
                        line=node.lineno, symbol=symbol,
                        message=(
                            "`.item()` inside a jitted function forces a "
                            "device->host sync at trace time"
                        ),
                    )
                elif name in ("np.asarray", "np.array", "numpy.asarray",
                              "numpy.array", "onp.asarray", "onp.array"):
                    yield Finding(
                        rule=RULE_HOST_SYNC, path=src.relpath,
                        line=node.lineno, symbol=symbol,
                        message=(
                            f"`{name}(...)` inside a jitted function "
                            "materializes a traced value on the host"
                        ),
                    )
                elif name in ("float", "int") and node.args:
                    p = mentions_param(node.args[0])
                    if p is not None:
                        yield Finding(
                            rule=RULE_HOST_SYNC, path=src.relpath,
                            line=node.lineno, symbol=symbol,
                            message=(
                                f"`{name}({p})` on a traced parameter "
                                "inside a jitted function is a host sync "
                                "(concretization error under jit)"
                            ),
                        )
            elif isinstance(node, (ast.If, ast.While)):
                p = mentions_param(node.test)
                if p is not None:
                    yield Finding(
                        rule=RULE_TRACED_FLOW, path=src.relpath,
                        line=node.lineno, symbol=symbol,
                        message=(
                            f"Python `{type(node).__name__.lower()}` on "
                            f"traced parameter `{p}` inside a jitted "
                            "function — use lax.cond/lax.select or mark "
                            "it static"
                        ),
                    )
