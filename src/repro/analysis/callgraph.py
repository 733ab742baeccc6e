"""AST module index + jit-boundary reachability for repro-lint.

The checkers share one picture of the code: every function definition in
the analyzed tree (nested defs included), where names imported into each
module resolve to, which functions are *jit roots* (passed to `jax.jit`
or `pl.pallas_call`, directly or through `jax.vmap` / `functools.partial`
/ decorator forms), and which functions are *traced* — reachable from a
root through calls the index can resolve repo-locally.

Resolution is deliberately best-effort and syntactic: `api.decode_step`
resolves through the module's imports, `self.method()` resolves inside
the enclosing class, and the tuple-unpack idiom

    greedy, sample = make_decode_fns(cfg, dsg=..., chunk=..., max_seq=...)
    self._jit_decode = (jax.jit(greedy, donate_argnums=(3,), ...), ...)

resolves because the index records which nested defs a function returns.
Anything it cannot resolve it drops silently — the checkers trade recall
for zero-configuration precision (docs/analysis.md spells out the
contract).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple


@dataclass
class FunctionInfo:
    qualname: str                 # "pkg.mod.Class.method" / "pkg.mod.fn.inner"
    local: str                    # qualname without the module prefix
    node: ast.AST                 # FunctionDef | AsyncFunctionDef | Lambda
    module: "ModuleInfo"
    class_name: Optional[str] = None
    parent: Optional[str] = None  # enclosing function's local qualname
    returned_inner: Tuple[str, ...] = ()   # local names of returned nested defs


@dataclass
class JitRoot:
    func: FunctionInfo
    kind: str                     # "jit" | "pallas"
    static_argnums: Tuple[int, ...] = ()
    static_argnames: Tuple[str, ...] = ()
    call_line: int = 0

    def static_params(self) -> Set[str]:
        """Parameter names the jit boundary treats as static."""
        node = self.func.node
        if isinstance(node, ast.Lambda):
            args = node.args
        else:
            args = node.args
        names = [a.arg for a in args.posonlyargs + args.args]
        out = set(self.static_argnames)
        for i in self.static_argnums:
            if 0 <= i < len(names):
                out.add(names[i])
        return out


@dataclass
class ModuleInfo:
    path: Path
    relpath: str                  # analysis-root-relative, for findings
    modname: str                  # dotted module name
    tree: ast.Module
    functions: Dict[str, FunctionInfo] = field(default_factory=dict)
    classes: Dict[str, ast.ClassDef] = field(default_factory=dict)
    imports: Dict[str, str] = field(default_factory=dict)  # alias -> dotted


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for nested Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class Index:
    """Cross-module function index over a set of Python files."""

    def __init__(self):
        self.modules: Dict[str, ModuleInfo] = {}       # modname -> info
        self.functions: Dict[str, FunctionInfo] = {}   # qualname -> info

    # -- construction --------------------------------------------------------

    @classmethod
    def build(cls, root, files: Optional[List[Path]] = None) -> "Index":
        root = Path(root)
        idx = cls()
        if files is None:
            files = sorted(p for p in root.rglob("*.py")
                           if "__pycache__" not in p.parts)
        for path in files:
            rel = path.relative_to(root)
            modname = ".".join(rel.with_suffix("").parts)
            if modname.endswith(".__init__"):
                modname = modname[:-len(".__init__")]
            try:
                tree = ast.parse(path.read_text(encoding="utf-8"))
            except SyntaxError:
                continue
            mi = ModuleInfo(path=path, relpath=str(rel), modname=modname,
                            tree=tree)
            idx._index_module(mi)
            idx.modules[modname] = mi
        return idx

    def _index_module(self, mi: ModuleInfo):
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    mi.imports[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:          # relative import
                    parts = mi.modname.split(".")
                    parts = parts[:len(parts) - node.level]
                    base = ".".join(parts + ([node.module]
                                             if node.module else []))
                for a in node.names:
                    if a.name == "*":
                        continue
                    mi.imports[a.asname or a.name] = f"{base}.{a.name}"

        def visit(body, prefix: str, class_name: Optional[str],
                  parent: Optional[str]):
            for node in body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    local = f"{prefix}{node.name}"
                    fi = FunctionInfo(
                        qualname=f"{mi.modname}.{local}", local=local,
                        node=node, module=mi, class_name=class_name,
                        parent=parent)
                    fi.returned_inner = self._returned_inner(node)
                    mi.functions[local] = fi
                    self.functions[fi.qualname] = fi
                    visit(node.body, f"{local}.", class_name, local)
                elif isinstance(node, ast.ClassDef):
                    mi.classes[node.name] = node
                    visit(node.body, f"{node.name}.", node.name, parent)
                elif isinstance(node, (ast.If, ast.Try, ast.With, ast.For,
                                       ast.While)):
                    # defs nested under control flow keep the same prefix
                    visit(getattr(node, "body", []), prefix, class_name,
                          parent)
                    visit(getattr(node, "orelse", []), prefix, class_name,
                          parent)
                    visit(getattr(node, "finalbody", []), prefix,
                          class_name, parent)
                    for h in getattr(node, "handlers", []):
                        visit(h.body, prefix, class_name, parent)

        visit(mi.tree.body, "", None, None)

    @staticmethod
    def _returned_inner(fn) -> Tuple[str, ...]:
        """Local names of nested defs this function returns (supports
        `return inner` and `return inner_a, inner_b`)."""
        inner = {n.name for n in fn.body
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for node in ast.walk(fn):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            val = node.value
            names: List[str] = []
            elts = val.elts if isinstance(val, ast.Tuple) else [val]
            for e in elts:
                if isinstance(e, ast.Name) and e.id in inner:
                    names.append(e.id)
                else:
                    break
            else:
                if names:
                    return tuple(names)
        return ()

    # -- name resolution -----------------------------------------------------

    def resolve_function(self, mi: ModuleInfo, name: str,
                         scope: Optional[str] = None,
                         class_name: Optional[str] = None
                         ) -> Optional[FunctionInfo]:
        """Resolve a (possibly dotted) name used in module `mi` inside
        function `scope` to a FunctionInfo, or None."""
        # nested def in the enclosing function chain
        cur = scope
        while cur is not None:
            fi = mi.functions.get(f"{cur}.{name}")
            if fi is not None:
                return fi
            cur = mi.functions[cur].parent if cur in mi.functions else None
        # method of the enclosing class
        if class_name and f"{class_name}.{name}" in mi.functions:
            return mi.functions[f"{class_name}.{name}"]
        # module-level def
        if name in mi.functions:
            return mi.functions[name]
        # imported: "api.decode_step" or direct "from x import fn"
        parts = name.split(".")
        head = parts[0]
        target = mi.imports.get(head)
        if target is None:
            return None
        full = ".".join([target] + parts[1:])
        # full is e.g. "repro.models.api.decode_step": split module/attr
        for cut in range(len(full.split(".")), 0, -1):
            modname = ".".join(full.split(".")[:cut])
            rest = ".".join(full.split(".")[cut:])
            m = self.modules.get(modname)
            if m is not None:
                return m.functions.get(rest) if rest else None
        return None

    # -- jit roots -----------------------------------------------------------

    JIT_NAMES = {"jax.jit", "jit"}
    PALLAS_NAMES = {"pl.pallas_call", "pallas_call"}
    WRAPPERS = {"jax.vmap", "vmap", "partial", "functools.partial",
                "jax.pmap", "pmap"}

    def jit_roots(self) -> List[JitRoot]:
        roots: Dict[str, JitRoot] = {}
        for mi in self.modules.values():
            for scope, call, deco_target in self._jit_sites(mi):
                fn_expr, statics = self._unwrap_jit(call)
                if deco_target is not None:
                    fi = deco_target
                else:
                    fi = self._resolve_fn_expr(mi, scope, fn_expr)
                if fi is None:
                    continue
                kind = ("pallas"
                        if self._callee_name(call) in self.PALLAS_NAMES
                        else "jit")
                root = JitRoot(func=fi, kind=kind,
                               static_argnums=statics[0],
                               static_argnames=statics[1],
                               call_line=getattr(call, "lineno", 0))
                roots.setdefault(fi.qualname, root)
        return list(roots.values())

    def _callee_name(self, call: ast.Call) -> Optional[str]:
        return dotted(call.func)

    def _jit_sites(self, mi: ModuleInfo):
        """Yield (enclosing_scope, call_node, decorated_fn|None) for every
        jax.jit / pl.pallas_call site, including decorator forms."""
        # decorator forms
        for fi in mi.functions.values():
            node = fi.node
            for deco in getattr(node, "decorator_list", []):
                name = dotted(deco) or ""
                if name in self.JIT_NAMES:
                    fake = ast.Call(func=deco, args=[], keywords=[])
                    ast.copy_location(fake, deco)
                    yield fi.parent, fake, fi
                elif isinstance(deco, ast.Call):
                    dname = dotted(deco.func) or ""
                    if dname in self.JIT_NAMES:
                        yield fi.parent, deco, fi
                    elif dname in ("partial", "functools.partial") \
                            and deco.args \
                            and (dotted(deco.args[0]) or "") \
                            in self.JIT_NAMES:
                        yield fi.parent, deco, fi
        # call forms: jax.jit(fn, ...) / pl.pallas_call(kernel, ...)
        for scope, fnode in [(None, mi.tree)] + [
                (fi.local, fi.node) for fi in mi.functions.values()]:
            for node in ast.walk(fnode):
                if not isinstance(node, ast.Call):
                    continue
                name = self._callee_name(node) or ""
                if name in self.JIT_NAMES or name in self.PALLAS_NAMES:
                    yield scope, node, None

    def _unwrap_jit(self, call: ast.Call):
        """(fn_expr, (static_argnums, static_argnames)) from a jit-ish
        call, unwrapping partial/vmap."""
        nums: Tuple[int, ...] = ()
        names: Tuple[str, ...] = ()
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                nums = self._const_ints(kw.value)
            elif kw.arg == "static_argnames":
                names = self._const_strs(kw.value)
        fn_expr = None
        args = list(call.args)
        # partial(jax.jit, ...) decorator: no fn arg beyond jax.jit itself
        if args and (dotted(args[0]) or "") in self.JIT_NAMES:
            args = args[1:]
        if args:
            fn_expr = args[0]
        return fn_expr, (nums, names)

    def _resolve_fn_expr(self, mi: ModuleInfo, scope, expr
                         ) -> Optional[FunctionInfo]:
        if expr is None:
            return None
        if isinstance(expr, ast.Call):
            name = self._callee_name(expr) or ""
            if name in self.WRAPPERS and expr.args:
                return self._resolve_fn_expr(mi, scope, expr.args[0])
            return None
        name = dotted(expr)
        if name is None:
            return None
        fi = self.resolve_function(mi, name, scope=scope)
        if fi is not None:
            return fi
        # tuple-unpack binding: greedy, sample = make_decode_fns(cfg)
        return self._tuple_unpack_binding(mi, scope, name)

    def _tuple_unpack_binding(self, mi: ModuleInfo, scope, name: str
                              ) -> Optional[FunctionInfo]:
        """Resolve `name` bound by `a, b = f(...)` where f returns its
        nested defs, anywhere in the enclosing scope chain (or module
        body for scope None)."""
        bodies = []
        cur = scope
        while cur is not None and cur in mi.functions:
            bodies.append(mi.functions[cur].node)
            cur = mi.functions[cur].parent
        bodies.append(mi.tree)
        for body in bodies:
            for node in ast.walk(body):
                if not isinstance(node, ast.Assign) \
                        or not isinstance(node.value, ast.Call):
                    continue
                targets = node.targets[0]
                elts = (targets.elts if isinstance(targets, ast.Tuple)
                        else [targets])
                tnames = [e.id if isinstance(e, ast.Name) else None
                          for e in elts]
                if name not in tnames:
                    continue
                callee = self._callee_name(node.value)
                if callee is None:
                    continue
                producer = self.resolve_function(mi, callee, scope=scope)
                if producer is None or not producer.returned_inner:
                    continue
                pos = tnames.index(name)
                if pos < len(producer.returned_inner):
                    inner_local = (f"{producer.local}."
                                   f"{producer.returned_inner[pos]}")
                    return producer.module.functions.get(inner_local)
        return None

    @staticmethod
    def _const_ints(node) -> Tuple[int, ...]:
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return (node.value,)
        if isinstance(node, (ast.Tuple, ast.List)):
            return tuple(e.value for e in node.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, int))
        return ()

    @staticmethod
    def _const_strs(node) -> Tuple[str, ...]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return (node.value,)
        if isinstance(node, (ast.Tuple, ast.List)):
            return tuple(e.value for e in node.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str))
        return ()

    # -- reachability --------------------------------------------------------

    def traced_functions(self, roots: List[JitRoot]
                         ) -> Dict[str, FunctionInfo]:
        """Functions reachable from the jit roots through resolvable
        calls — the set the tracer actually walks."""
        seen: Dict[str, FunctionInfo] = {}
        work = [r.func for r in roots]
        while work:
            fi = work.pop()
            if fi.qualname in seen:
                continue
            seen[fi.qualname] = fi
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted(node.func)
                if name is None:
                    continue
                if name.startswith("self."):
                    callee = self.resolve_function(
                        fi.module, name[len("self."):],
                        scope=fi.local, class_name=fi.class_name)
                else:
                    callee = self.resolve_function(fi.module, name,
                                                   scope=fi.local)
                if callee is not None:
                    work.append(callee)
        return seen
