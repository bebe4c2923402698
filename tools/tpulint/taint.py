"""Intra-function taint analysis over the AST.

"Tainted" = the expression may hold (or derive from) a live tensor/tracer
value at runtime. Sources are framework idioms, not type inference:
``Tensor(...)``/``as_tensor(...)``/``_t(...)`` constructions, ``*._data``
payload reads, ``dispatch.call`` results, ``jnp.*``/``jax.*`` results, and
the parameters of lowering functions handed to ``dispatch.call`` (those run
under trace, so their arguments are tracers). Taint propagates through
arithmetic, indexing, methods, containers — and through ``np.*`` calls: the
``np`` call itself is the host-sync finding (TPU104), and its result is a
host copy of tensor data, so a later ``float()`` on it is still part of the
same graph break (how `loss.py edit_distance`'s ``float(dp[n])`` is found).

The walk runs twice per scope so names tainted on a loop back-edge are seen
by earlier lines; findings dedup on (line, col, code).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from .core import SourceFile

TENSOR_FACTORIES = {"_t", "as_tensor", "to_tensor", "Tensor", "t"}
SYNC_METHODS = {"numpy": "TPU101", "item": "TPU102", "tolist": "TPU102"}
CAST_BUILTINS = {"float", "int", "bool", "complex"}
#: attributes that are static metadata even on a tensor (trace-safe)
SAFE_ATTRS = {"shape", "ndim", "dtype", "size", "name", "place",
              "stop_gradient", "grad_node", "output_index", "is_leaf"}
#: builtins whose results never carry tensor data
UNTAINTED_CALLS = {"len", "isinstance", "issubclass", "hasattr", "type",
                   "id", "print", "repr", "str", "format", "range",
                   "callable", "getattr", "dir", "vars"}
#: jax/jnp calls returning static metadata (dtypes, backend names) or
#: host-side callable wrappers (jit/eval_shape), not device values —
#: truthiness on these is trace-safe
METADATA_CALLS = {"issubdtype", "isdtype", "result_type", "can_cast",
                  "promote_types", "iinfo", "finfo", "dtype",
                  "default_backend", "device_count", "local_device_count",
                  "devices", "local_devices", "process_index",
                  "process_count", "is_initialized", "get_abstract_mesh",
                  "jit", "eval_shape",
                  "ShapeDtypeStruct", "tree_structure"}

FIXITS = {
    "TPU101": "keep the computation in-graph (jnp ops / registered ops); "
              "materialize only at explicit host boundaries",
    "TPU102": "use jnp indexing/reductions instead of host scalars",
    "TPU103": "use jnp arithmetic; for data-dependent branching use "
              "static.nn.cond / static.nn.while_loop",
    "TPU104": "use the jnp.* equivalent so XLA keeps the op on device",
    "TPU105": "use static.nn.cond (compiles to lax.cond, one XLA program)",
    "TPU106": "use static.nn.while_loop (compiles to lax.while_loop)",
    "TPU201": "thread the tensor through function returns/pytrees; module "
              "state outlives the trace and leaks the tracer",
    "TPU202": "default to None and construct inside the function body",
    "TPU203": "key caches on static metadata (shape/dtype), never on "
              "tensor values — tracer hashes poison the cache",
}


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _dotted(node) -> str:
    """'a.b.c' for nested attributes rooted at a Name, else ''."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


class ModuleInfo:
    """Module-level facts the per-scope analysis consults."""

    def __init__(self, tree: ast.Module):
        self.np_aliases: Set[str] = set()
        self.jnp_aliases: Set[str] = set()
        self.module_mutables: Set[str] = set()
        self.lowering_fn_names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    alias = a.asname or a.name.split(".")[0]
                    if a.name == "numpy":
                        self.np_aliases.add(alias)
                    elif a.name in ("jax.numpy", "jax"):
                        self.jnp_aliases.add(alias)
            elif isinstance(node, ast.ImportFrom):
                if node.module == "jax" and any(a.name == "numpy"
                                                for a in node.names):
                    for a in node.names:
                        if a.name == "numpy":
                            self.jnp_aliases.add(a.asname or "numpy")
            elif isinstance(node, ast.Call):
                # dispatch.call("op", f, ...): f's params are tracers
                if (_dotted(node.func).endswith("dispatch.call")
                        or _dotted(node.func) == "call") and len(node.args) >= 2:
                    if isinstance(node.args[1], ast.Name):
                        self.lowering_fn_names.add(node.args[1].id)
        for stmt in tree.body:
            if isinstance(stmt, ast.Assign) and self._is_mutable(stmt.value):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        self.module_mutables.add(t.id)

    @staticmethod
    def _is_mutable(v) -> bool:
        if isinstance(v, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                          ast.ListComp, ast.SetComp)):
            return True
        if isinstance(v, ast.Call) and _call_name(v) in (
                "dict", "list", "set", "defaultdict", "OrderedDict",
                "WeakValueDictionary"):
            return True
        return False


class ScopeAnalyzer:
    """Runs the taint walk over one function (or the module body)."""

    def __init__(self, sf: SourceFile, info: ModuleInfo, enabled: Set[str],
                 seen: Set):
        self.sf = sf
        self.info = info
        self.enabled = enabled
        self.seen = seen          # (line, col, code) dedup, shared per file
        self.tainted: Set[str] = set()
        self.dict_names: Set[str] = set(info.module_mutables)
        self.globals_decl: Set[str] = set()
        self.vararg_names: Set[str] = set()
        self.emit_findings = False   # only on the final walk

    def flag(self, node, code: str, message: str):
        if not self.emit_findings or code not in self.enabled:
            return
        k = (node.lineno, node.col_offset, code)
        if k in self.seen:
            return
        self.seen.add(k)
        self.sf.add(node.lineno, node.col_offset, code, message,
                    FIXITS.get(code, ""))

    # -- expression taint (emits sync findings as a side effect) ----------
    def expr(self, node) -> bool:
        if node is None or isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr == "_data":
                return True
            base = self.expr(node.value)
            if node.attr in SAFE_ATTRS:
                return False
            return base
        if isinstance(node, ast.Subscript):
            self.expr(node.slice)
            return self.expr(node.value)
        if isinstance(node, ast.BinOp):
            l, r = self.expr(node.left), self.expr(node.right)
            return l or r
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any([self.expr(v) for v in node.values])
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                for c in node.comparators:
                    self.expr(c)
                self.expr(node.left)
                return False      # identity checks are trace-safe
            if all(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops):
                # membership depends on the KEY being tensor-derived; a
                # static-keyed container merely holding tensors is safe
                left = self.expr(node.left)
                for c in node.comparators:
                    self.expr(c)
                return left
            parts = [self.expr(node.left)] + [self.expr(c)
                                              for c in node.comparators]
            return any(parts)
        if isinstance(node, ast.IfExp):
            self.expr(node.test)
            b, o = self.expr(node.body), self.expr(node.orelse)
            return b or o
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any([self.expr(e) for e in node.elts])
        if isinstance(node, ast.Dict):
            ks = [self.expr(k) for k in node.keys if k is not None]
            vs = [self.expr(v) for v in node.values]
            return any(ks) or any(vs)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        if isinstance(node, ast.Slice):
            for part in (node.lower, node.upper, node.step):
                self.expr(part)
            return False
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            return self._comprehension(node)
        if isinstance(node, ast.Lambda):
            return False
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    self.expr(v.value)
            return False
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, (ast.Await, ast.Yield, ast.YieldFrom)):
            return self.expr(getattr(node, "value", None))
        if isinstance(node, ast.NamedExpr):
            t = self.expr(node.value)
            if isinstance(node.target, ast.Name):
                self._bind(node.target.id, t)
            return t
        return False

    def _comprehension(self, node) -> bool:
        saved = set(self.tainted)
        for gen in node.generators:
            it = self.expr(gen.iter)
            # bind the target either way: an UNTAINTED iterable must
            # CLEAR stale taint on a shadowing target name (the
            # two-pass back-edge union otherwise leaks a tensor-loop
            # variable's taint into a later metadata comprehension
            # reusing the name — augmented-assign/truthiness FPs)
            for n in ast.walk(gen.target):
                if isinstance(n, ast.Name):
                    self._bind(n.id, it)
            for cond in gen.ifs:
                if self.expr(cond):
                    self.flag(cond, "TPU105",
                              "comprehension filter predicated on a tensor "
                              "value forces a host sync per element")
        if isinstance(node, ast.DictComp):
            k, v = self.expr(node.key), self.expr(node.value)
            out = k or v
        else:
            out = self.expr(node.elt)
        self.tainted = saved
        return out

    def _call(self, node: ast.Call) -> bool:
        name = _call_name(node)
        dotted = _dotted(node.func)
        root = dotted.split(".")[0] if dotted else ""
        arg_taints = [self.expr(a) for a in node.args]
        arg_taints += [self.expr(k.value) for k in node.keywords]
        any_arg = any(arg_taints)

        # ---- sync points -------------------------------------------------
        if isinstance(node.func, ast.Attribute) and name in SYNC_METHODS:
            if self.expr(node.func.value):
                self.flag(node, SYNC_METHODS[name],
                          f"host sync: .{name}() materializes a tensor to "
                          "the host")
                return False      # result is a host scalar/ndarray copy
        if isinstance(node.func, ast.Name) and name in CAST_BUILTINS:
            if any_arg:
                self.flag(node, "TPU103",
                          f"host sync: {name}() forces a tensor-derived "
                          "value to a python scalar")
                return False
        if root in self.info.np_aliases and root != "":
            if any_arg:
                self.flag(node, "TPU104",
                          f"host sync: {dotted}() pulls tensor-derived data "
                          "through numpy on the host")
            return any_arg        # host COPY of tensor data stays tracked

        # ---- taint-producing calls ---------------------------------------
        if isinstance(node.func, ast.Name) and name in TENSOR_FACTORIES:
            return True
        if dotted.endswith("dispatch.call") or dotted in (
                "call", "Tensor", "as_tensor", "to_tensor", "paddle.to_tensor"):
            return True
        if root in self.info.jnp_aliases and root != "":
            return name not in METADATA_CALLS
        if name in UNTAINTED_CALLS and isinstance(node.func, ast.Name):
            return False
        if isinstance(node.func, ast.Attribute):
            # method on a tainted object keeps the data tensor-derived
            if self.expr(node.func.value):
                return True
        return any_arg

    def _predicate_taint(self, test) -> bool:
        """Taint of an if/while test. Truthiness of a bare ``*args`` name
        is an ARITY check (``if rest:`` for an optional input) — trace-safe
        even though the tuple's elements are tracers. Likewise the bare
        truthiness of a name KNOWN to be a python container (bound from a
        dict/list/set literal or comprehension) is an EMPTINESS check:
        the container may hold tensors, but ``bool()`` never touches its
        elements (``if not params:`` / ``if state_dict:``)."""
        safe_names = self.vararg_names | self.dict_names
        if isinstance(test, ast.Name) and test.id in safe_names:
            return False
        if (isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not)
                and isinstance(test.operand, ast.Name)
                and test.operand.id in safe_names):
            return False
        return self.expr(test)

    # -- statements -------------------------------------------------------
    def _bind(self, name: str, taint: bool):
        if taint:
            self.tainted.add(name)
        else:
            self.tainted.discard(name)

    def _assign_target(self, target, taint: bool, value=None):
        if isinstance(target, ast.Name):
            if target.id in self.globals_decl and taint:
                self.flag(target, "TPU201",
                          f"tensor value assigned to module global "
                          f"'{target.id}' — outlives the trace (leaked "
                          "tracer)")
            self._bind(target.id, taint)
            if value is not None and ModuleInfo._is_mutable(value):
                self.dict_names.add(target.id)
            elif value is not None:
                # re-bound to a non-container: the emptiness-check
                # exemption must not outlive the container binding
                self.dict_names.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            if (value is not None and isinstance(value, (ast.Tuple, ast.List))
                    and len(value.elts) == len(target.elts)):
                for t, v in zip(target.elts, value.elts):
                    self._assign_target(t, self.expr(v), v)
            else:
                for t in target.elts:
                    self._assign_target(t, taint)
        elif isinstance(target, ast.Starred):
            self._assign_target(target.value, taint)
        elif isinstance(target, ast.Subscript):
            key_taint = self.expr(target.slice)
            base = target.value
            if isinstance(base, ast.Name):
                if key_taint and base.id in self.dict_names:
                    self.flag(target, "TPU203",
                              f"container '{base.id}' keyed on a tensor "
                              "value")
                if base.id in self.info.module_mutables and taint:
                    self.flag(target, "TPU201",
                              f"tensor value stored into module-level "
                              f"container '{base.id}'")
                if taint:
                    # writing tensor-derived data into a slot taints the
                    # whole container (edit_distance: dp[c] = ... min(s1 != s2))
                    self.tainted.add(base.id)
        elif isinstance(target, ast.Attribute):
            self.expr(target.value)

    def stmt(self, node):
        if isinstance(node, ast.Assign):
            taint = self.expr(node.value)
            for t in node.targets:
                self._assign_target(t, taint, node.value)
        elif isinstance(node, ast.AnnAssign):
            taint = self.expr(node.value) if node.value else False
            ann = _dotted(node.annotation) if node.annotation else ""
            if ann.split(".")[-1] == "Tensor":
                taint = True
            if node.target is not None:
                self._assign_target(node.target, taint, node.value)
        elif isinstance(node, ast.AugAssign):
            taint = self.expr(node.value)
            if isinstance(node.target, ast.Name):
                if taint:
                    if node.target.id in self.globals_decl:
                        self.flag(node.target, "TPU201",
                                  f"tensor value accumulated into module "
                                  f"global '{node.target.id}'")
                    self.tainted.add(node.target.id)
            else:
                self._assign_target(node.target, taint)
        elif isinstance(node, ast.If):
            if self._predicate_taint(node.test):
                self.flag(node, "TPU105",
                          "`if` on a tensor value graph-breaks capture "
                          "(host sync per trace)")
            self.body(node.body)
            self.body(node.orelse)
        elif isinstance(node, ast.While):
            if self._predicate_taint(node.test):
                self.flag(node, "TPU106",
                          "`while` on a tensor value graph-breaks capture "
                          "(host sync per iteration)")
            self.body(node.body)
            self.body(node.orelse)
        elif isinstance(node, ast.For):
            it = self.expr(node.iter)
            # re-binding semantics: a loop over an UNTAINTED iterable
            # clears stale taint on its target names (e.g. ``for t in
            # range(3)`` after an earlier tensor loop reused ``t`` — the
            # back-edge union otherwise flags ``n += t`` / ``if t:``)
            for n in ast.walk(node.target):
                if isinstance(n, ast.Name):
                    self._bind(n.id, it)
            self.body(node.body)
            self.body(node.orelse)
        elif isinstance(node, ast.With):
            for item in node.items:
                self.expr(item.context_expr)
                if item.optional_vars is not None:
                    self._assign_target(item.optional_vars, False)
            self.body(node.body)
        elif isinstance(node, ast.Try):
            self.body(node.body)
            for h in node.handlers:
                self.body(h.body)
            self.body(node.orelse)
            self.body(node.finalbody)
        elif isinstance(node, ast.Global):
            self.globals_decl.update(node.names)
        elif isinstance(node, (ast.Return, ast.Expr, ast.Delete,
                               ast.Raise, ast.Assert)):
            for child in ast.iter_child_nodes(node):
                self.expr(child)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            pass  # nested scopes handled by the module driver
        elif isinstance(node, ast.ClassDef):
            self.body(node.body)

    def body(self, stmts):
        for s in stmts:
            self.stmt(s)

    def run(self, stmts, param_taints: Optional[Dict[str, bool]] = None):
        if param_taints:
            for n, t in param_taints.items():
                self._bind(n, t)
        base = set(self.tainted)
        # pass 1: silent, to reach names tainted on loop back-edges
        self.emit_findings = False
        self.body(stmts)
        looped = set(self.tainted)
        self.tainted = base | looped
        self.emit_findings = True
        self.body(stmts)


def _function_scopes(tree: ast.Module):
    """Yield (funcdef, enclosing-class-or-None) for every function."""
    out = []

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(child)
                walk(child)
            elif isinstance(child, ast.ClassDef):
                walk(child)
            elif isinstance(child, (ast.If, ast.Try, ast.With, ast.For,
                                    ast.While)):
                walk(child)
    walk(tree)
    return out


def analyze_file(sf: SourceFile, enabled: Set[str]):
    """Run the taint passes over one file, appending findings to ``sf``."""
    try:
        tree = ast.parse(sf.text, filename=sf.path)
    except SyntaxError as e:
        sf.add(e.lineno or 1, 0, "TPU100", f"syntax error: {e.msg}")
        return
    info = ModuleInfo(tree)
    seen: Set = set()

    # module body (imports/constants) — analyzed as its own scope
    top = ScopeAnalyzer(sf, info, enabled, seen)
    top.run([s for s in tree.body
             if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef))])
    module_taint = set(top.tainted)

    for fn in _function_scopes(tree):
        an = ScopeAnalyzer(sf, info, enabled, seen)
        an.tainted = set(module_taint)
        params: Dict[str, bool] = {}
        args = fn.args
        all_args = (args.posonlyargs + args.args + args.kwonlyargs
                    + ([args.vararg] if args.vararg else [])
                    + ([args.kwarg] if args.kwarg else []))
        is_lowering = fn.name in info.lowering_fn_names
        if args.vararg:
            an.vararg_names.add(args.vararg.arg)
        for a in all_args:
            ann = _dotted(a.annotation) if a.annotation else ""
            params[a.arg] = (is_lowering and a.arg != "self") or \
                ann.split(".")[-1] == "Tensor"
        # TPU202: mutable defaults retain whatever the trace puts in them
        if "TPU202" in enabled:
            for d in list(args.defaults) + [d for d in args.kw_defaults if d]:
                if ModuleInfo._is_mutable(d):
                    sf.add(d.lineno, d.col_offset, "TPU202",
                           f"mutable default argument in '{fn.name}' — "
                           "retains tensors/tracers across calls",
                           FIXITS["TPU202"])
        an.run(fn.body, params)
