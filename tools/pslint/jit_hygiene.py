"""Checker 2 — JIT-hygiene (PSL2xx).

The recompile/stall hazard classes the bug log paid for at runtime:

PSL201  ``jax.jit``/``jax.pmap`` *constructed* inside a loop body or a
        handler-thread method — every construction is a fresh cache
        entry, and a compile landing mid-fill, concurrent with threaded
        worker dispatch, stalls the fill (the PR 4 ``_norm_fn``
        incident).  Build programs once, at ``compile_step`` time.
PSL202  host-sync inside a jitted function: ``.item()``,
        ``np.asarray``/``np.array``, ``jax.device_get``, or
        ``float()``/``int()``/``bool()`` applied to a traced parameter —
        a tracer leak that either fails at trace time or silently
        devolves the program to per-call host round trips.
PSL203  a jit-built handle (``self.X = jax.jit(...)``) *invoked* from a
        handler-thread method: the first call compiles, and a compile on
        a conn/worker thread races the serve loop's dispatch (the same
        stall class).  Keep jitted-program invocation on the serve loop,
        prewarmed at compile time.
"""

from __future__ import annotations

import ast

from .core import (CorpusIndex, Finding, FunctionStackVisitor, SourceModule,
                   class_methods, dotted_name, is_self_attr, iter_hierarchy)

RULE = "jit-hygiene"

_JIT_NAMES = {"jax.jit", "jax.pmap"}
_HOST_SYNC_FNS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                  "jax.device_get"}
_CAST_BUILTINS = {"float", "int", "bool"}


def _is_jit_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and dotted_name(node.func) in _JIT_NAMES)


def _function_params(fn) -> "set[str]":
    a = fn.args
    names = [p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n != "self"}


def _jitted_function_defs(mod: SourceModule) -> "list[ast.FunctionDef]":
    """Functions the module hands to ``jax.jit``/``jax.pmap``: named args
    anywhere inside the jit call (covers ``jax.jit(jax.shard_map(body,
    ...))``), plus ``@jax.jit``-decorated defs."""
    defs = {n.name: n for n in mod.nodes
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    jitted: dict[str, ast.FunctionDef] = {}
    for node in mod.nodes:
        if _is_jit_call(node):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id in defs:
                    jitted[sub.id] = defs[sub.id]
    for fn in defs.values():
        for dec in fn.decorator_list:
            names = {dotted_name(dec)}
            if isinstance(dec, ast.Call):  # @partial(jax.jit, ...)
                names |= {dotted_name(a) for a in dec.args}
            if names & _JIT_NAMES:
                jitted[fn.name] = fn
    return list(jitted.values())


def _check_jitted_body(mod: SourceModule, fn, findings: list) -> None:
    params = _function_params(fn)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if (isinstance(func, ast.Attribute) and func.attr == "item"
                and not node.args):
            findings.append(Finding(
                mod.path, node.lineno, "PSL202", RULE,
                f".item() inside jitted function {fn.name!r} is a host "
                f"sync / tracer leak",
                hint="compute on-device and sync once, outside the jitted "
                     "program"))
            continue
        name = dotted_name(func)
        if name in _HOST_SYNC_FNS:
            findings.append(Finding(
                mod.path, node.lineno, "PSL202", RULE,
                f"{name}() inside jitted function {fn.name!r} breaks "
                f"tracing (host materialization inside the program)",
                hint="use jnp equivalents inside jit; convert to numpy "
                     "outside the jitted program"))
            continue
        if (isinstance(func, ast.Name) and func.id in _CAST_BUILTINS
                and node.args):
            touched = {n.id for n in ast.walk(node.args[0])
                       if isinstance(n, ast.Name)}
            if touched & params:
                findings.append(Finding(
                    mod.path, node.lineno, "PSL202", RULE,
                    f"{func.id}() applied to traced parameter(s) "
                    f"{sorted(touched & params)} inside jitted function "
                    f"{fn.name!r} — float(tracer) host-syncs",
                    hint="keep the value as a jax array; cast with "
                         ".astype / jnp builtins inside jit"))


def check(corpus: list[SourceModule],
          index: "CorpusIndex | None" = None) -> list[Finding]:
    findings: list[Finding] = []
    index = index or CorpusIndex(corpus)
    classes = index.classes

    for mod in corpus:
        # PSL202: host syncs inside jitted function bodies.
        for fn in _jitted_function_defs(mod):
            _check_jitted_body(mod, fn, findings)

        # PSL201 (loop half): walk with loop-depth tracking.
        class Scan(FunctionStackVisitor):
            def __init__(self):
                super().__init__()
                self.loop_depth = 0

            def visit_For(self, node):
                self.loop_depth += 1
                self.generic_visit(node)
                self.loop_depth -= 1

            visit_While = visit_For

            def visit_Call(self, node):
                if _is_jit_call(node) and self.loop_depth > 0:
                    findings.append(Finding(
                        mod.path, node.lineno, "PSL201", RULE,
                        f"{dotted_name(node.func)}() constructed inside a "
                        f"loop body — a fresh program (and compile) per "
                        f"iteration",
                        hint="hoist construction out of the loop (build "
                             "once at compile_step time and reuse the "
                             "handle)"))
                self.generic_visit(node)

        Scan().visit(mod.tree)

    # PSL201 (handler half) + PSL203: need per-class thread contexts.
    handle_cache: "dict[str, set[str]]" = {}
    for mod, cls in index.class_list:
        methods = index.methods(cls)
        contexts = index.contexts(cls)
        # jit-built handles of this class — unioned over EVERY class in
        # the hierarchy, not the name-deduped method map: a subclass
        # overriding compile_step (and calling super()) would otherwise
        # shadow the base method that does the assigning.  (Each class
        # body is walked once; the hierarchy union reuses the cache.)
        handles: "set[str]" = set()
        for c in iter_hierarchy(cls, classes):
            if c.name not in handle_cache:
                handle_cache[c.name] = {
                    t.attr for node in ast.walk(c)
                    if isinstance(node, ast.Assign)
                    and _is_jit_call(node.value)
                    for t in node.targets if is_self_attr(t)}
            handles |= handle_cache[c.name]
        for name, meth in class_methods(cls).items():
            if "handler-thread" not in contexts.get(name, ()):
                continue
            for node in ast.walk(meth):
                if not isinstance(node, ast.Call):
                    continue
                if _is_jit_call(node):
                    findings.append(Finding(
                        mod.path, node.lineno, "PSL201", RULE,
                        f"{dotted_name(node.func)}() constructed in "
                        f"{cls.name}.{name}, a handler-thread method — "
                        f"the compile races the serve loop's dispatch "
                        f"(observed to stall the fill)",
                        hint="construct at compile_step time; handler "
                             "threads only enqueue"))
                elif (is_self_attr(node.func)
                        and node.func.attr in handles):
                    findings.append(Finding(
                        mod.path, node.lineno, "PSL203", RULE,
                        f"jitted handle self.{node.func.attr} invoked "
                        f"from {cls.name}.{name} (handler-thread "
                        f"context) — a first-call compile here races "
                        f"the serve loop (the mid-fill-compile stall "
                        f"class)",
                        hint="invoke jitted programs from the serve "
                             "loop only, prewarmed at compile time; "
                             "handler threads hand data over queues"))
    return findings
