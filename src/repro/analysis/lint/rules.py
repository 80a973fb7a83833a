"""Repo-specific AST lint rules (stdlib ``ast`` — no third-party linter).

Each rule is a function ``(module: Module) -> list[Finding]`` registered in
``RULES`` under its code. The rules encode *trace discipline*: invariants that
keep jit-traced code correct and recompile-bounded, which generic linters
cannot know about. Codes:

======  ======================================================================
RA101   Python ``if``/``while`` branching on a traced (jnp/lax) expression
        inside a traced module — control flow must be ``lax.cond``/``where``.
RA102   ``jax.jit`` with ``static_argnums``/``static_argnames`` naming a
        parameter whose default is an unhashable literal (list/dict/set).
RA103   ``custom_vjp`` residual-arity mismatch: the bwd function must return
        one cotangent per *differentiable* primal argument (positional args
        minus ``nondiff_argnums``); the fwd function must return a 2-tuple.
RA104   Import-time JAX device work: module-level calls to ``jnp.*`` /
        ``jax.random.*`` / ``jax.devices`` / ``jax.device_put`` allocate or
        touch devices before any ``main()`` can configure them.
RA105   Nondeterminism in traced modules: ``time`` / ``random`` imports or
        calls — traced code must draw randomness from threaded PRNG keys.
RA106   Host synchronization in traced modules: ``.item()``,
        ``jax.device_get``, ``np.asarray``/``np.array`` force a device sync
        inside what should be a pure traced hot path.
RA107   Unused import (F401-lite fallback for environments without ruff).
        ``__init__.py`` re-exports and ``# noqa``-marked lines are exempt.
RA108   Raw wall-clock reads (``time.time``/``time.perf_counter``/
        ``time.monotonic`` and their ``_ns`` variants) in *instrumented*
        modules — timing there must go through ``repro.obs.clock`` (or an
        injected clock) so FakeClock tests and traced runs see one time
        source. See :data:`INSTRUMENTED_MODULES`.
======  ======================================================================

"Traced modules" (RA101/RA105/RA106) are the files whose function bodies run
under ``jit``/``shard_map``/``custom_vjp`` — see :data:`TRACED_MODULES`. Host
orchestration (trainer loop, serve engine host side, benchmarks) is
deliberately out of scope: ``time.time()`` around a step is fine there —
*except* in the obs-instrumented modules, where RA108 routes it through the
injectable obs clock (``time.sleep`` stays allowed: it waits, it doesn't
measure).
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Callable, Optional

from ..report import Finding

# Files (repo-relative, '/'-separated; prefixes for directories) whose
# function bodies are traced. Keep in sync with DESIGN.md §11.
TRACED_MODULES = (
    "src/repro/core/",
    "src/repro/kernels/",
    "src/repro/train/gnn_step.py",
    "src/repro/train/compression.py",
    "src/repro/train/optimizer.py",
)

# Files (repo-relative; prefixes for directories) instrumented through
# repro.obs — their timing must read the injectable obs clock, never the
# wall clock directly (RA108). benchmarks/ are exempt: they *measure* the
# instrumentation, so they need an independent time source.
INSTRUMENTED_MODULES = (
    "src/repro/serve/",
    "src/repro/store/",
    "src/repro/train/trainer.py",
    "src/repro/launch/scenarios.py",
)

# jax attribute calls that are pure metadata — allowed at import time (RA104).
_IMPORT_TIME_OK = {"ShapeDtypeStruct", "tree_util", "custom_vjp", "custom_jvp",
                   "jit", "vmap", "grad", "value_and_grad", "named_scope"}


@dataclasses.dataclass
class Module:
    """One parsed file handed to every rule."""

    relpath: str          # repo-relative, '/'-separated
    tree: ast.Module
    lines: list[str]

    @property
    def is_traced(self) -> bool:
        return _matches(self.relpath, TRACED_MODULES)

    @property
    def is_instrumented(self) -> bool:
        return _matches(self.relpath, INSTRUMENTED_MODULES)

    def noqa(self, lineno: int) -> bool:
        if 1 <= lineno <= len(self.lines):
            return "# noqa" in self.lines[lineno - 1]
        return False


def _matches(relpath: str, prefixes) -> bool:
    return any(relpath == p or (p.endswith("/") and relpath.startswith(p))
               for p in prefixes)


RULES: dict[str, Callable[[Module], list[Finding]]] = {}


def rule(code: str):
    def deco(fn):
        RULES[code] = fn
        return fn
    return deco


def _attr_root(node: ast.AST) -> Optional[str]:
    """Leftmost name of an attribute chain (``jnp.max(...)`` -> ``jnp``)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _attr_chain(node: ast.AST) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _finding(code: str, mod: Module, node: ast.AST, msg: str) -> Finding:
    return Finding(code=code, where=mod.relpath, message=msg,
                   line=getattr(node, "lineno", 0))


# ---------------------------------------------------------------------------
# RA101 — Python branching on traced values
# ---------------------------------------------------------------------------
@rule("RA101")
def traced_branch(mod: Module) -> list[Finding]:
    if not mod.is_traced:
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.If, ast.While)):
            continue
        for sub in ast.walk(node.test):
            root = _attr_root(sub) if isinstance(sub, (ast.Attribute,
                                                       ast.Call)) else None
            if isinstance(sub, ast.Call):
                root = _attr_root(sub.func)
            if root in ("jnp", "lax") and not mod.noqa(node.lineno):
                kind = "while" if isinstance(node, ast.While) else "if"
                out.append(_finding(
                    "RA101", mod, node,
                    f"python `{kind}` branches on a traced `{root}.*` "
                    "expression; use lax.cond/jnp.where (trace-time branching "
                    "forces recompilation or fails under jit)"))
                break
    return out


# ---------------------------------------------------------------------------
# RA102 — unhashable static args
# ---------------------------------------------------------------------------
_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set)


def _jit_static_params(call: ast.Call) -> Optional[tuple[list[int], list[str]]]:
    """(static positions, static names) if ``call`` configures jax.jit with
    static args — handles ``jax.jit(...)`` and ``partial(jax.jit, ...)``."""
    target = call.func
    if _attr_chain(target) in ("partial", "functools.partial") and call.args:
        inner = call.args[0]
        if _attr_chain(inner) not in ("jax.jit", "jit"):
            return None
    elif _attr_chain(target) not in ("jax.jit", "jit"):
        return None
    nums: list[int] = []
    names: list[str] = []
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.append(n.value)
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.append(n.value)
    if not nums and not names:
        return None
    return nums, names


@rule("RA102")
def unhashable_static_args(mod: Module) -> list[Finding]:
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for deco in node.decorator_list:
            if not isinstance(deco, ast.Call):
                continue
            static = _jit_static_params(deco)
            if static is None:
                continue
            nums, names = static
            args = node.args.args
            defaults = node.args.defaults
            offset = len(args) - len(defaults)
            for i, a in enumerate(args):
                if i < offset:
                    continue
                default = defaults[i - offset]
                if not isinstance(default, _MUTABLE_LITERALS):
                    continue
                if (i in nums or a.arg in names) and not mod.noqa(node.lineno):
                    out.append(_finding(
                        "RA102", mod, node,
                        f"static arg {a.arg!r} of jitted {node.name!r} "
                        f"defaults to an unhashable "
                        f"{type(default).__name__.lower()} literal — jit "
                        "static args must be hashable"))
    return out


# ---------------------------------------------------------------------------
# RA103 — custom_vjp fwd/bwd residual arity
# ---------------------------------------------------------------------------
def _custom_vjp_info(fn: ast.FunctionDef) -> Optional[tuple[int, set[int]]]:
    """(n positional args, nondiff positions) when ``fn`` is a custom_vjp
    primal — ``@jax.custom_vjp`` or ``@partial(jax.custom_vjp, ...)``."""
    for deco in fn.decorator_list:
        chain = _attr_chain(deco if not isinstance(deco, ast.Call)
                            else deco.func)
        nondiff: set[int] = set()
        if isinstance(deco, ast.Call) and chain in ("partial",
                                                    "functools.partial"):
            if not deco.args or _attr_chain(deco.args[0]) not in (
                    "jax.custom_vjp", "custom_vjp"):
                continue
            for kw in deco.keywords:
                if kw.arg == "nondiff_argnums":
                    for n in ast.walk(kw.value):
                        if isinstance(n, ast.Constant) and \
                                isinstance(n.value, int):
                            nondiff.add(n.value)
        elif chain not in ("jax.custom_vjp", "custom_vjp"):
            continue
        if fn.args.vararg is not None:
            return None  # *args defeat static arity counting
        return len(fn.args.args), nondiff
    return None


@rule("RA103")
def custom_vjp_arity(mod: Module) -> list[Finding]:
    fns = {n.name: n for n in ast.walk(mod.tree)
           if isinstance(n, ast.FunctionDef)}
    primals = {name: info for name, fn in fns.items()
               if (info := _custom_vjp_info(fn)) is not None}
    out = []
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "defvjp" and len(node.args) >= 2):
            continue
        primal = _attr_root(node.func)
        if primal not in primals:
            continue
        n_args, nondiff = primals[primal]
        want = n_args - len(nondiff)
        fwd, bwd = (a.id if isinstance(a, ast.Name) else None
                    for a in node.args[:2])
        for name, expect, what in ((fwd, 2, "fwd (out, residuals)"),
                                   (bwd, want, "bwd cotangent")):
            fn = fns.get(name)
            if fn is None:
                continue
            for ret in ast.walk(fn):
                if isinstance(ret, ast.Return) and \
                        isinstance(ret.value, ast.Tuple) and \
                        len(ret.value.elts) != expect and \
                        not mod.noqa(ret.lineno):
                    out.append(_finding(
                        "RA103", mod, ret,
                        f"{name} returns a {len(ret.value.elts)}-tuple but "
                        f"custom_vjp {primal!r} needs a {expect}-tuple "
                        f"({what}; {n_args} positional args, "
                        f"{len(nondiff)} nondiff)"))
    return out


# ---------------------------------------------------------------------------
# RA104 — import-time JAX device work
# ---------------------------------------------------------------------------
def _module_level_nodes(tree: ast.Module):
    """Statements executed at import: everything except function bodies."""
    stack = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                stack.append(child)


@rule("RA104")
def import_time_device_work(mod: Module) -> list[Finding]:
    out = []
    for node in _module_level_nodes(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        root = chain.split(".")[0] if chain else None
        bad = (root == "jnp"
               or chain.startswith("jax.numpy.")
               or chain.startswith("jax.random.")
               or chain in ("jax.devices", "jax.device_put",
                            "jax.device_get", "jax.eval_shape"))
        if root == "jax" and chain.split(".")[-1] in _IMPORT_TIME_OK:
            bad = False
        if bad and not mod.noqa(node.lineno):
            out.append(_finding(
                "RA104", mod, node,
                f"module-level `{chain}(...)` runs JAX device work at import "
                "time (allocates/initializes backends before main() can "
                "configure them)"))
    return out


# ---------------------------------------------------------------------------
# RA105 — nondeterminism in traced modules
# ---------------------------------------------------------------------------
@rule("RA105")
def nondeterminism(mod: Module) -> list[Finding]:
    if not mod.is_traced:
        return []
    out = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] in ("time", "random") and \
                        not mod.noqa(node.lineno):
                    out.append(_finding(
                        "RA105", mod, node,
                        f"`import {a.name}` in a traced module — traced code "
                        "must be deterministic (PRNG keys, not "
                        "wall-clock/global RNG)"))
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] in ("time", "random") \
                    and not mod.noqa(node.lineno):
                out.append(_finding(
                    "RA105", mod, node,
                    f"`from {node.module} import ...` in a traced module"))
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain.split(".")[0] in ("time", "random") and \
                    not mod.noqa(node.lineno):
                out.append(_finding(
                    "RA105", mod, node,
                    f"`{chain}(...)` in a traced module — nondeterministic "
                    "under jit (called at trace time, frozen thereafter)"))
    return out


# ---------------------------------------------------------------------------
# RA106 — host synchronization in traced modules
# ---------------------------------------------------------------------------
_HOST_SYNC_CALLS = ("jax.device_get", "np.asarray", "np.array",
                    "numpy.asarray", "numpy.array")


@rule("RA106")
def host_sync(mod: Module) -> list[Finding]:
    if not mod.is_traced:
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "item" and not node.args:
            if not mod.noqa(node.lineno):
                out.append(_finding(
                    "RA106", mod, node,
                    "`.item()` in a traced module forces a host sync "
                    "(blocks the device stream; fails under jit)"))
            continue
        chain = _attr_chain(node.func)
        if chain in _HOST_SYNC_CALLS and not mod.noqa(node.lineno):
            out.append(_finding(
                "RA106", mod, node,
                f"`{chain}(...)` in a traced module pulls values to the "
                "host — hot paths must stay on device"))
    return out


# ---------------------------------------------------------------------------
# RA107 — unused imports (F401-lite; ruff owns this when available)
# ---------------------------------------------------------------------------
@rule("RA107")
def unused_imports(mod: Module) -> list[Finding]:
    if mod.relpath.endswith("__init__.py"):
        return []  # __init__ imports are the package's public re-exports
    imported: dict[str, tuple[int, str]] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                imported[name] = (node.lineno, a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name != "*":
                    imported[a.asname or a.name] = (node.lineno, a.name)
    used = {n.id for n in ast.walk(mod.tree) if isinstance(n, ast.Name)}
    # names exported via __all__ count as used
    for node in mod.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    for c in ast.walk(node.value):
                        if isinstance(c, ast.Constant) and \
                                isinstance(c.value, str):
                            used.add(c.value)
    out = []
    for name, (lineno, orig) in sorted(imported.items()):
        if name in used or mod.noqa(lineno):
            continue
        out.append(Finding(
            code="RA107", where=mod.relpath, line=lineno,
            message=f"unused import {orig!r}"))
    return out


# ---------------------------------------------------------------------------
# RA108 — raw wall-clock reads in obs-instrumented modules
# ---------------------------------------------------------------------------
_WALLCLOCK_NAMES = ("time", "perf_counter", "monotonic",
                    "perf_counter_ns", "monotonic_ns")
_WALLCLOCK_CALLS = tuple(f"time.{n}" for n in _WALLCLOCK_NAMES)


@rule("RA108")
def raw_wallclock(mod: Module) -> list[Finding]:
    if not mod.is_instrumented:
        return []
    # `from time import perf_counter [as pc]` makes the read a bare-name
    # call — track the local aliases so the rename doesn't evade the rule
    aliases: set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name in _WALLCLOCK_NAMES:
                    aliases.add(a.asname or a.name)
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if (chain in _WALLCLOCK_CALLS or chain in aliases) and \
                not mod.noqa(node.lineno):
            out.append(_finding(
                "RA108", mod, node,
                f"`{chain}(...)` reads the wall clock directly in an "
                "obs-instrumented module — use repro.obs.clock() (or an "
                "injected clock) so FakeClock tests and traces share one "
                "time source"))
    return out
