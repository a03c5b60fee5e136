"""jaxlint: the RL6xx/RL7xx checker family — TPU/JAX compute-plane hazards.

JAX's trace-then-compile model makes the compute plane's performance bugs
statically recognizable in a way eager frameworks never were: a retrace, a
host sync, or a donated-buffer read each leave a syntactic fingerprint.
These checkers only run over files that import jax (see `uses_jax`).

Shared analysis infrastructure, built in a prepass over the whole file:

- **Jit registry**: names/attributes bound to `jax.jit(...)` results —
  module globals (`_step = jax.jit(f)`), instance attributes
  (`self._jit_decode = jax.jit(...)`), program-cache dict attributes
  (`self._jit_prefill[key] = jax.jit(...)`), and functions whose return
  value is a jit result (factories like `build_train_step`). A call through
  any of these is a "jitted call".
- **Device taint**: expressions that hold device arrays — results of jitted
  calls, `jnp.*` constructors, `jax.device_put`, instance attributes
  assigned device values anywhere in the class, and anything reached from a
  tainted value through subscripts/attributes/tuple unpacking. Host
  conversions (`np.asarray`, `float`, `int`) both *clear* taint and are the
  sync sites RL603 reports.
- **Hot-context call graph**: a function is hot when it contains a sync
  site inside a lexical loop, or when it is called (transitively, within
  this file) from a loop body — the decode/train step loops reach their
  helpers through exactly this shape.
"""

from __future__ import annotations

import ast
import re
from typing import Optional

from ray_tpu.devtools.raylint.core import FileContext, Finding

_JIT_NAMES = {"jit", "pjit"}
_JNP_ROOTS = {"jnp"}
_ARRAY_CTORS = {"zeros", "ones", "empty", "full", "arange", "asarray", "array"}
_SYNC_BUILTINS = {"float", "int", "bool"}
_EVICT_METHODS = {"pop", "popitem", "clear"}
# wrapper name -> positions of the function argument(s) it traces.
_TRACING_WRAPPERS = {
    "jit": (0,), "pjit": (0,), "scan": (0,), "shard_map": (0,),
    "vmap": (0,), "pmap": (0,), "grad": (0,), "value_and_grad": (0,),
    "checkpoint": (0,), "remat": (0,), "while_loop": (0, 1),
    "cond": (1, 2), "fori_loop": (2,), "custom_vjp": (0,),
    "custom_jvp": (0,),
}

_USES_JAX_RE = re.compile(r"^\s*(import jax\b|from jax\b|import jax\.)",
                          re.MULTILINE)


def uses_jax(source: str) -> bool:
    return bool(_USES_JAX_RE.search(source))


def _base_ident(expr: ast.expr) -> Optional[str]:
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Subscript):
        return _base_ident(expr.value)
    return None


def _root_name(expr: ast.expr) -> Optional[str]:
    while isinstance(expr, (ast.Attribute, ast.Subscript)):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def _dotted(expr: ast.expr) -> Optional[str]:
    """`jax.lax.scan` -> "jax.lax.scan"; bare names -> the name."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_ctor(node: ast.expr) -> bool:
    """`jax.jit(...)` / `pjit(...)` / `jax.experimental.pjit.pjit(...)`."""
    if not isinstance(node, ast.Call):
        return False
    dotted = _dotted(node.func)
    if dotted is None:
        return False
    last = dotted.rsplit(".", 1)[-1]
    return last in _JIT_NAMES


def _argnums_donated(node: ast.Call) -> tuple:
    """Positional donate indices of a jit ctor call (donate_argnums only —
    donate_argnames needs kw callsites, matched separately)."""
    for kw in node.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                return tuple(
                    e.value for e in v.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, int)
                )
    return ()


def _is_jnp_call(node: ast.expr) -> bool:
    if not isinstance(node, ast.Call):
        return False
    dotted = _dotted(node.func)
    if not dotted:
        return False
    root = dotted.split(".", 1)[0]
    if root in _JNP_ROOTS:
        return True
    return dotted in ("jax.device_put", "jax.numpy") or dotted.startswith(
        "jax.numpy."
    ) or dotted.startswith("jax.random.")


def _contains_len_call(expr: ast.expr) -> bool:
    for node in ast.walk(expr):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len"):
            return True
    return False


def _is_unbucketed_array_ctor(expr: ast.expr) -> bool:
    """np/jnp array ctor whose shape argument embeds a raw `len(...)`."""
    if not isinstance(expr, ast.Call):
        return False
    dotted = _dotted(expr.func) or ""
    last = dotted.rsplit(".", 1)[-1]
    root = dotted.split(".", 1)[0]
    if root not in ("np", "numpy", "jnp") or last not in _ARRAY_CTORS:
        return False
    return any(_contains_len_call(a) for a in expr.args[:1])


class _Prepass(ast.NodeVisitor):
    """File-wide facts every per-function check needs."""

    def __init__(self, tree: ast.AST):
        self.module_jit: set[str] = set()          # global names bound to jit
        self.jit_attrs: set[str] = set()           # self attrs bound to jit
        self.jit_dict_attrs: set[str] = set()      # self attrs: dict of programs
        self.device_attrs: set[str] = set()        # self attrs holding arrays
        self.jit_factories: set[str] = set()       # fns returning a jit result
        self.device_factories: set[str] = set()    # fns returning device arrays
        # traced-function references, scope-qualified so `jax.jit(update)`
        # inside Learner._build_update marks the NESTED `update`, never a
        # same-named public method: ("scope:<qualified ref scope>", name) for
        # bare names, ("class:<Class>", attr) for self.<method> references.
        self.jit_target_refs: set[tuple[str, str]] = set()
        self.donate: dict[str, tuple] = {}         # jit name/attr -> argnums
        # call graph: qualified fn -> (callees from loop bodies, all callees)
        self._calls_in_loops: dict[str, set[str]] = {}
        self._calls_all: dict[str, set[str]] = {}
        self._scope: list[str] = []
        self._class_stack: list[str] = []
        self._loop_depth = 0
        self._walk(tree)
        self.hot_functions = self._compute_hot()

    def _walk(self, tree):
        self.visit(tree)

    def _fn_key(self) -> str:
        return ".".join(self._scope) if self._scope else "<module>"

    def visit_ClassDef(self, node):
        self._scope.append(node.name)
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()
        self._scope.pop()

    def _visit_fn(self, node):
        self._scope.append(node.name)
        saved = self._loop_depth
        self._loop_depth = 0
        self._calls_in_loops.setdefault(self._fn_key(), set())
        self._calls_all.setdefault(self._fn_key(), set())
        self.generic_visit(node)
        self._loop_depth = saved
        self._scope.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _visit_loop(self, node):
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop
    visit_ListComp = _visit_loop
    visit_SetComp = _visit_loop
    visit_DictComp = _visit_loop
    visit_GeneratorExp = _visit_loop

    def visit_Return(self, node):
        if node.value is not None and self._scope:
            if _is_jit_ctor(node.value):
                self.jit_factories.add(self._scope[-1])
            elif isinstance(node.value, ast.Call):
                # `return self._jit_step(...)` — a plain method fronting a
                # jitted program returns device arrays (requires the jit
                # binding to appear earlier in the file, the common shape).
                f = node.value.func
                if (isinstance(f, ast.Attribute)
                        and isinstance(f.value, ast.Name)
                        and f.value.id in ("self", "cls")
                        and f.attr in self.jit_attrs) or (
                    isinstance(f, ast.Name) and f.id in self.module_jit
                ) or (
                    isinstance(f, ast.Subscript)
                    and _base_ident(f) in self.jit_dict_attrs
                ):
                    self.device_factories.add(self._scope[-1])
        self.generic_visit(node)

    def visit_Assign(self, node):
        value = node.value
        if _is_jit_ctor(value):
            donated = _argnums_donated(value)
            for t in node.targets:
                if isinstance(t, ast.Name):
                    if not self._scope:
                        self.module_jit.add(t.id)
                    if donated:
                        self.donate[t.id] = donated
                elif isinstance(t, ast.Attribute) and _root_name(t) in (
                    "self", "cls"
                ):
                    self.jit_attrs.add(t.attr)
                    if donated:
                        self.donate[t.attr] = donated
                elif isinstance(t, ast.Subscript):
                    ident = _base_ident(t)
                    if ident:
                        self.jit_dict_attrs.add(ident)
        elif self._value_is_devicey(value):
            for t in node.targets:
                if isinstance(t, ast.Attribute) and _root_name(t) in (
                    "self", "cls"
                ):
                    self.device_attrs.add(t.attr)
        # An empty dict attr later filled with programs registers at the
        # fill site (the Subscript branch above), not here.
        self.generic_visit(node)

    def _value_is_devicey(self, value: ast.expr) -> bool:
        """Does the assigned expression (or anything inside a container
        display / comprehension it builds) produce device arrays?"""
        for node in ast.walk(value):
            if _is_jnp_call(node):
                return True
        return False

    def visit_Call(self, node):
        # Tracing wrappers: jax.jit(f) / lax.scan(step, ...) /
        # shard_map(body, ...) mark f as a traced (jit-target) function.
        dotted = _dotted(node.func)
        last = dotted.rsplit(".", 1)[-1] if dotted else None
        if last in _TRACING_WRAPPERS:
            for pos in _TRACING_WRAPPERS[last]:
                if pos >= len(node.args):
                    continue
                arg = node.args[pos]
                if isinstance(arg, ast.Name):
                    self.jit_target_refs.add(
                        ("scope:" + ".".join(self._scope), arg.id)
                    )
                elif isinstance(arg, ast.Attribute) and isinstance(
                    arg.value, ast.Name
                ) and arg.value.id in ("self", "cls") and self._class_stack:
                    self.jit_target_refs.add(
                        ("class:" + self._class_stack[-1], arg.attr)
                    )
        # call graph edges
        if self._scope:
            callee = None
            if isinstance(node.func, ast.Name):
                callee = node.func.id
            elif isinstance(node.func, ast.Attribute) and _root_name(
                node.func
            ) in ("self", "cls"):
                callee = node.func.attr
            if callee:
                key = self._fn_key()
                self._calls_all.setdefault(key, set()).add(callee)
                if self._loop_depth:
                    self._calls_in_loops.setdefault(key, set()).add(callee)
        self.generic_visit(node)

    def _compute_hot(self) -> set[str]:
        """Functions reachable from a loop body: seeded by direct
        called-from-loop edges, closed over same-file calls. Matching is by
        trailing name segment (self.foo() can't see which class defines foo)."""
        hot: set[str] = set()
        for callees in self._calls_in_loops.values():
            hot |= callees
        changed = True
        while changed:
            changed = False
            for key, callees in self._calls_all.items():
                leaf = key.rsplit(".", 1)[-1]
                if leaf in hot:
                    new = callees - hot
                    if new:
                        hot |= new
                        changed = True
        return hot


class _JaxChecker(ast.NodeVisitor):
    def __init__(self, ctx: FileContext, pre: _Prepass):
        self.ctx = ctx
        self.pre = pre
        self.findings: list[Finding] = []
        self._scope: list[str] = []
        self._class_stack: list[str] = []
        self._func_stack: list[ast.AST] = []
        self._async_stack: list[bool] = []
        self._loop_depth = 0
        # per-function state
        self._tainted: list[set[str]] = []
        self._local_jit: list[dict[str, tuple]] = []   # name -> donate argnums
        self._list_locals: list[set[str]] = []
        self._unbucketed_locals: list[set[str]] = []
        # donation reads: (call line, donated root names) per function
        self._donation_calls: list[list[tuple[int, list[str]]]] = []

    # -- bookkeeping --------------------------------------------------------

    def _symbol(self) -> str:
        return ".".join(self._scope) if self._scope else "<module>"

    def _emit(self, node: ast.AST, code: str, message: str):
        self.findings.append(Finding(
            self.ctx.relpath, getattr(node, "lineno", 0), code, message,
            self._symbol(),
        ))

    def check_module(self):
        self.visit(self.ctx.tree)
        return self

    def visit_ClassDef(self, node):
        self._scope.append(node.name)
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()
        self._scope.pop()

    def _visit_fn(self, node, is_async: bool):
        self._scope.append(node.name)
        self._func_stack.append(node)
        self._async_stack.append(is_async)
        self._tainted.append(set())
        self._local_jit.append({})
        self._list_locals.append(set())
        self._unbucketed_locals.append(set())
        self._donation_calls.append([])
        saved_depth = self._loop_depth
        self._loop_depth = 0
        if self._is_jit_target(node) or self._is_jit_decorated(node):
            self._check_side_effects(node)
        self.generic_visit(node)
        self._check_donation_reads(node)
        self._loop_depth = saved_depth
        self._donation_calls.pop()
        self._unbucketed_locals.pop()
        self._list_locals.pop()
        self._local_jit.pop()
        self._tainted.pop()
        self._async_stack.pop()
        self._func_stack.pop()
        self._scope.pop()

    def visit_FunctionDef(self, node):
        self._visit_fn(node, is_async=False)

    def visit_AsyncFunctionDef(self, node):
        self._visit_fn(node, is_async=True)

    def _is_jit_target(self, node) -> bool:
        """Was THIS def (not a name-collision elsewhere) handed to a tracing
        wrapper? Methods match a `self.<name>` reference from their own class;
        nested/module defs match a bare-name reference from a scope the def is
        visible in (its defining scope or anything nested inside it)."""
        parent = self._scope[:-1]
        if parent and parent[-1] == (
            self._class_stack[-1] if self._class_stack else None
        ):
            return ("class:" + parent[-1], node.name) in self.pre.jit_target_refs
        prefix = ".".join(parent)
        for kind, name in self.pre.jit_target_refs:
            if name != node.name or not kind.startswith("scope:"):
                continue
            ref_scope = kind[len("scope:"):]
            if not prefix or ref_scope == prefix or ref_scope.startswith(
                prefix + "."
            ):
                return True
        return False

    @staticmethod
    def _is_jit_decorated(node) -> bool:
        for dec in node.decorator_list:
            if _is_jit_ctor(dec):
                return True
            dotted = _dotted(dec) or (
                _dotted(dec.func) if isinstance(dec, ast.Call) else None
            )
            if dotted and dotted.rsplit(".", 1)[-1] in _JIT_NAMES:
                return True
            if isinstance(dec, ast.Call):  # partial(jax.jit, ...)
                for a in dec.args:
                    d = _dotted(a)
                    if d and d.rsplit(".", 1)[-1] in _JIT_NAMES:
                        return True
        return False

    def _visit_loop(self, node):
        if isinstance(node, (ast.For, ast.AsyncFor)) and self._is_tainted(
            node.iter
        ):
            # iterating device state binds device values to the loop target
            self._taint_targets([node.target], True)
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop

    def _visit_comp(self, node):
        for gen in node.generators:
            if self._is_tainted(gen.iter):
                self._taint_targets([gen.target], True)
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_ListComp = _visit_comp
    visit_SetComp = _visit_comp
    visit_DictComp = _visit_comp
    visit_GeneratorExp = _visit_comp

    # -- hot-context predicate ---------------------------------------------

    def _in_hot_context(self) -> bool:
        if self._loop_depth:
            return True
        if self._async_stack and self._async_stack[-1]:
            return True
        return bool(self._scope) and self._scope[-1] in self.pre.hot_functions

    # -- taint --------------------------------------------------------------

    def _is_jitted_callable(self, func: ast.expr) -> bool:
        """Is this call-expression's func a known jitted program?"""
        if isinstance(func, ast.Name):
            return (func.id in self.pre.module_jit
                    or (self._local_jit and func.id in self._local_jit[-1]))
        if isinstance(func, ast.Attribute):
            root = _root_name(func)
            if root in ("self", "cls") and func.attr in self.pre.jit_attrs:
                return True
            return False
        if isinstance(func, ast.Subscript):
            ident = _base_ident(func)
            return bool(ident and ident in self.pre.jit_dict_attrs)
        return False

    def _is_jit_factory_call(self, value: ast.expr) -> bool:
        if not isinstance(value, ast.Call):
            return False
        name = None
        if isinstance(value.func, ast.Name):
            name = value.func.id
        elif isinstance(value.func, ast.Attribute):
            name = value.func.attr
        return bool(name and name in self.pre.jit_factories)

    def _is_tainted(self, expr: ast.expr) -> bool:
        if isinstance(expr, ast.Name):
            return bool(self._tainted and expr.id in self._tainted[-1])
        if isinstance(expr, ast.Attribute):
            root = _root_name(expr)
            if root in ("self", "cls"):
                # `self._caches[i][0]` reaches a device attr through its base
                return expr.attr in self.pre.device_attrs
            return self._is_tainted(expr.value)
        if isinstance(expr, ast.Subscript):
            return self._is_tainted(expr.value)
        if isinstance(expr, ast.Call):
            if self._is_jitted_callable(expr.func):
                return True
            if _is_jnp_call(expr):
                return True
            fname = None
            if isinstance(expr.func, ast.Name):
                fname = expr.func.id
            elif isinstance(expr.func, ast.Attribute):
                fname = expr.func.attr
            if fname and fname in self.pre.device_factories:
                return True
            # `.copy()` / `.astype()` / `.at[..].set(..)` on tainted stays device
            if isinstance(expr.func, ast.Attribute):
                return self._is_tainted(expr.func.value)
        return False

    def _taint_targets(self, targets, tainted: bool):
        if not self._tainted:
            return
        for t in targets:
            if isinstance(t, ast.Name):
                if tainted:
                    self._tainted[-1].add(t.id)
                else:
                    self._tainted[-1].discard(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                self._taint_targets(t.elts, tainted)

    # -- assignments: taint flow, RL602, RL604 locals, RL605 registry -------

    def visit_Assign(self, node: ast.Assign):
        value = node.value
        # RHS first: `x = [f(x) for x in np.asarray(x)]` must see the OLD
        # taint of x while walking the comprehension, not the post-store one.
        self.visit(value)
        for t in node.targets:
            self.visit(t)
        if _is_jit_ctor(value):
            donated = _argnums_donated(value)
            for t in node.targets:
                if isinstance(t, ast.Name) and self._local_jit:
                    self._local_jit[-1][t.id] = donated
                elif isinstance(t, ast.Subscript):
                    self._check_unbounded_cache(node, t)
            return
        if self._is_jit_factory_call(value) or (
            isinstance(value, ast.Name) and self._local_jit
            and value.id in self._local_jit[-1]
        ):
            # a program (from a factory or an alias) stored into a dict
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    self._check_unbounded_cache(node, t)
                elif isinstance(t, ast.Name) and self._local_jit:
                    self._local_jit[-1][t.id] = ()
            return
        tainted = self._is_tainted(value)
        self._taint_targets(node.targets, tainted)
        if self._list_locals:
            is_list = isinstance(value, (ast.List, ast.ListComp)) or (
                isinstance(value, ast.Call)
                and isinstance(value.func, ast.Name)
                and value.func.id == "list"
            )
            for t in node.targets:
                if isinstance(t, ast.Name):
                    if is_list:
                        self._list_locals[-1].add(t.id)
                    else:
                        self._list_locals[-1].discard(t.id)
                    if _is_unbucketed_array_ctor(value):
                        self._unbucketed_locals[-1].add(t.id)
                    else:
                        self._unbucketed_locals[-1].discard(t.id)

    def _check_unbounded_cache(self, node, target: ast.Subscript):
        """RL602: a jitted program stored into a cache with no eviction in
        sight. Evidence of bounding, checked across the enclosing function:
        `.pop()/.popitem()/.clear()` on the same cache, `del cache[...]`, or a
        `len(cache)` read (a cap check)."""
        ident = _base_ident(target)
        if not ident or not self._func_stack:
            return
        if self._has_eviction_evidence(self._func_stack[-1], ident):
            return
        self._emit(
            node, "RL602",
            f"jitted program stored into {ident!r} with no eviction or cap in "
            "this function: request-derived keys compile and retain programs "
            "unboundedly (an adversarial input mix exhausts memory); bound it "
            "with an explicit bucket set or LRU cap",
        )

    @staticmethod
    def _has_eviction_evidence(fn: ast.AST, ident: str) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                f = node.func
                if (isinstance(f, ast.Attribute) and f.attr in _EVICT_METHODS
                        and _base_ident(f.value) == ident):
                    return True
                if (isinstance(f, ast.Name) and f.id == "len" and node.args
                        and _base_ident(node.args[0]) == ident):
                    return True
            if isinstance(node, ast.Delete):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) and _base_ident(t) == ident:
                        return True
        return False

    # -- calls: RL601, RL603, RL604, RL605 ----------------------------------

    def visit_Call(self, node: ast.Call):
        if _is_jit_ctor(node):
            if self._loop_depth:
                self._emit(
                    node, "RL601",
                    "jax.jit(...) constructed inside a loop: every iteration "
                    "builds a fresh wrapper whose compiled program cannot be "
                    "reused across calls; hoist the jit to module/__init__ "
                    "scope or a keyed program cache",
                )
        elif isinstance(node.func, ast.Call) and _is_jit_ctor(node.func):
            if self._func_stack:
                self._emit(
                    node, "RL601",
                    "jax.jit(f)(...) constructed and invoked in one "
                    "expression inside a function: the wrapper dies with the "
                    "frame, so every call re-traces; cache the jitted "
                    "callable outside the per-call frame",
                )
        self._check_host_sync(node)
        if self._is_jitted_callable(node.func):
            self._check_retrace_args(node)
            self._record_donation_call(node)
        self.generic_visit(node)

    def _check_host_sync(self, node: ast.Call):
        """RL603: device->host synchronization in a hot context."""
        if not self._in_hot_context():
            return
        func = node.func
        reason = None
        target = None
        dotted = _dotted(func) or ""
        last = dotted.rsplit(".", 1)[-1]
        if isinstance(func, ast.Name) and func.id in _SYNC_BUILTINS:
            if node.args and self._is_tainted(node.args[0]):
                reason = f"{func.id}() on a device value"
                target = node.args[0]
        elif dotted in ("np.asarray", "np.array", "numpy.asarray",
                        "numpy.array"):
            if node.args and self._is_tainted(node.args[0]):
                reason = f"{dotted}() on a device value"
                target = node.args[0]
        elif last == "device_get":
            reason = "jax.device_get()"
            target = node.args[0] if node.args else node
        elif isinstance(func, ast.Attribute) and func.attr in (
            "item", "tolist", "block_until_ready"
        ):
            if func.attr == "block_until_ready" or self._is_tainted(
                func.value
            ):
                reason = f".{func.attr}()"
                target = func.value
        if reason is None:
            return
        name = None
        if target is not None:
            root = _root_name(target)
            name = _base_ident(target) if root in ("self", "cls") else root
        where = f" (value {name!r})" if name else ""
        self._emit(
            node, "RL603",
            f"host sync {reason}{where} inside a decode/train hot path "
            "(loop body, loop-called helper, or async frame) stalls the "
            "dispatch pipeline per step; batch the readback once per chunk, "
            "keep the state host-native, or annotate the sync as intentional",
        )

    def _check_retrace_args(self, node: ast.Call):
        """RL604: arguments whose pytree structure or shape varies with the
        data, passed to a jitted callable without static_argnums/bucketing."""
        for arg in node.args:
            if isinstance(arg, (ast.List, ast.ListComp)) or (
                isinstance(arg, ast.Name) and self._list_locals
                and arg.id in self._list_locals[-1]
            ):
                self._emit(
                    node, "RL604",
                    "Python list passed to a jitted callable: its pytree "
                    "structure (and so the compiled program) changes with the "
                    "list's length — every distinct length re-traces; pass an "
                    "array, or mark the argument static and bucket it",
                )
            elif _is_unbucketed_array_ctor(arg) or (
                isinstance(arg, ast.Name) and self._unbucketed_locals
                and arg.id in self._unbucketed_locals[-1]
            ):
                self._emit(
                    node, "RL604",
                    "array with a raw len()-derived shape passed to a jitted "
                    "callable: every distinct input length compiles a new "
                    "program; round the shape to a bucket table first",
                )

    # -- RL605: donated argument read after the call ------------------------

    def _record_donation_call(self, node: ast.Call):
        func = node.func
        donated: tuple = ()
        if isinstance(func, ast.Name) and self._local_jit and func.id in (
            self._local_jit[-1]
        ):
            donated = self._local_jit[-1][func.id]
        elif isinstance(func, ast.Attribute) and func.attr in self.pre.donate:
            donated = self.pre.donate[func.attr]
        elif isinstance(func, ast.Name) and func.id in self.pre.donate:
            donated = self.pre.donate[func.id]
        if not donated or not self._donation_calls:
            return
        roots = []
        for pos in donated:
            if pos < len(node.args):
                root = _root_name(node.args[pos])
                if root:
                    roots.append(root)
        if roots:
            self._donation_calls[-1].append((node.lineno, roots))

    def _check_donation_reads(self, fn: ast.AST):
        """After `out = jitted(x)` with x donated, a later read of x sees a
        deleted buffer (jax raises) or, worse on some paths, aliased memory."""
        if not self._donation_calls or not self._donation_calls[-1]:
            return
        calls = self._donation_calls[-1]
        assigns: dict[str, list[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    for leaf in ast.walk(t):
                        if isinstance(leaf, ast.Name):
                            assigns.setdefault(leaf.id, []).append(node.lineno)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)):
                continue
            for call_line, roots in calls:
                if node.id in roots and node.lineno > call_line:
                    # Reassigned at/after the donating call -> fresh value
                    # (`state, _ = step(state, ...)` rebinds on the call line).
                    if any(call_line <= a <= node.lineno
                           for a in assigns.get(node.id, [])):
                        continue
                    self.findings.append(Finding(
                        self.ctx.relpath, node.lineno, "RL605",
                        f"{node.id!r} was donated to a jitted call on line "
                        f"{call_line} (donate_argnums) and is read afterwards:"
                        " the buffer was handed to XLA and no longer holds "
                        "the value; rebind the name from the call's result",
                        self._symbol(),
                    ))

    # -- RL701: side effects inside traced functions -------------------------

    def _check_side_effects(self, fn: ast.AST):
        """A function handed to jit/scan/shard_map runs at TRACE time only:
        writes to self/globals/closures happen once per compilation, not per
        execution — silent state corruption the day the cache stops hitting."""
        local_names: set[str] = set()
        args = fn.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            local_names.add(a.arg)
        for a in (args.vararg, args.kwarg):
            if a is not None:
                local_names.add(a.arg)
        declared_global: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                # Nested defs trace with the outer function; their params and
                # name are locals of *some* traced frame, which is all the
                # closure check needs.
                a = node.args
                for p in (a.posonlyargs + a.args + a.kwonlyargs):
                    local_names.add(p.arg)
                for p in (a.vararg, a.kwarg):
                    if p is not None:
                        local_names.add(p.arg)
                if not isinstance(node, ast.Lambda):
                    local_names.add(node.name)
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                declared_global.update(node.names)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        local_names.add(t.id)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                if isinstance(node.target, ast.Name):
                    local_names.add(node.target.id)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                for t in ast.walk(node.target):
                    if isinstance(t, ast.Name):
                        local_names.add(t.id)
            elif isinstance(node, ast.comprehension):
                for t in ast.walk(node.target):
                    if isinstance(t, ast.Name):
                        local_names.add(t.id)
            elif isinstance(node, ast.With):
                for item in node.items:
                    if isinstance(item.optional_vars, ast.Name):
                        local_names.add(item.optional_vars.id)

        def emit(node, what):
            # self._scope already ends with fn's name (appended by _visit_fn).
            self.findings.append(Finding(
                self.ctx.relpath, node.lineno, "RL701",
                f"{what} inside a function handed to jax.jit/lax.scan/"
                "shard_map: the side effect runs at trace time (once per "
                "compilation), not per call — and a captured tracer here "
                "escapes the trace; return the new value instead",
                ".".join(self._scope),
            ))

        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        root = _root_name(t)
                        if root in ("self", "cls"):
                            emit(node, f"write to {root}.{_base_ident(t)}")
                        elif (root and root not in local_names
                              and isinstance(t, ast.Subscript)):
                            emit(node, f"write into closed-over {root!r}")
                    elif (isinstance(t, ast.Name)
                          and t.id in declared_global):
                        emit(node, f"write to global/nonlocal {t.id!r}")
            elif isinstance(node, ast.Expr) and isinstance(
                node.value, ast.Call
            ):
                # Only bare-statement mutator calls: `x.append(v)` is
                # mutation-for-effect; `new, st = tx.update(...)` is the
                # functional optax idiom whose result carries the state.
                f = node.value.func
                if isinstance(f, ast.Attribute) and f.attr in (
                    "append", "extend", "add", "update", "insert",
                    "setdefault", "pop", "remove", "clear",
                ):
                    root = _root_name(f.value)
                    if root in ("self", "cls"):
                        emit(node, f".{f.attr}() on {root} state")
                    elif root and root not in local_names and not isinstance(
                        f.value, ast.Call
                    ):
                        emit(node, f".{f.attr}() on closed-over {root!r}")


def check_jax_file(ctx: FileContext) -> list[Finding]:
    if not uses_jax(ctx.source):
        return []
    pre = _Prepass(ctx.tree)
    return _JaxChecker(ctx, pre).check_module().findings
