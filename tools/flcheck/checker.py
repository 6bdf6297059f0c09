"""flcheck: this repo's trace-safety & determinism invariants as lint rules.

Stdlib-``ast`` only (the offline CI container must run it with no extra
wheels, and it must never import the code it checks).  Every rule is named,
individually suppressible (``# flcheck: disable=FLC001`` on any line the
flagged node spans), and grounded in a bug this repo actually shipped:

  FLC001  ``jax.jit`` / ``jax.vmap`` / ``jax.pmap`` applied to a bound
          method or a local lambda at call time.  Each call builds a fresh
          function object, so the jit cache misses every time — the PR 7
          ``jax.jit(model.accuracy)`` bug (2.2x on the cells legacy sweep).
  FLC002  builtin ``hash()`` / ``id()``.  String hashing is salted per
          process (PYTHONHASHSEED) and ``id()`` is an address — seeds, PRNG
          folds and registry/init paths derived from either differ across
          processes — the PR 8 model-init bug (fixed with ``zlib.crc32``).
  FLC003  host-sync constructs (``float()`` / ``int()`` / ``bool()`` /
          ``.item()`` / ``np.asarray``) applied to traced values inside
          functions reachable from a ``@jit`` / ``lax.scan`` /
          ``lax.while_loop`` body (a lightweight call graph decides
          reachability).
  FLC004  Python int arithmetic crossing the ``jnp`` boundary without an
          explicit dtype — host ints above 2**31 - 1 silently overflow the
          default int32 (the PR 7 10^8-param payload-accounting bug).
  FLC005  ``log(1 + x)`` / ``1 - exp(x)`` where ``log1p`` / ``expm1``
          exist — catastrophic cancellation for small |x| (the PR 5 f32
          downlink-SNR underflow that poisoned the Fig. 5 time axis).
          Deliberately does NOT match ``log2(1 + SINR)``: that is the
          Shannon rate formula, bit-pinned across the scheduler tests.
  FLC006  a pinned error-message literal duplicated outside
          ``repro/core/errors.py`` (the FLConfig / ``ota.check_uplink``
          drift hazard) — the signatures are derived from that module's
          constants by parsing it, never importing it.
  FLC007  ``import hypothesis`` / ``import zstandard`` outside a
          ``try/except ImportError`` shim — the offline CI container does
          not ship either wheel (see requirements-dev.txt).
  FLC008  a host span or counter (``spans.span`` / ``spans.count`` /
          ``jax.profiler.TraceAnnotation``) inside jit-reachable code
          (FLC003's call graph): it runs once per trace, so the span times
          the tracing and the count counts compiles, not work.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re

RULES = {
    "FLC001": (
        "jit/vmap/pmap of a bound method or local lambda at call time — "
        "fresh function object per call misses the jit cache; hoist to a "
        "module-level function (model/config as static args)"
    ),
    "FLC002": (
        "builtin hash()/id() is PYTHONHASHSEED-/address-salted and differs "
        "across processes; derive seeds and registry paths from "
        "zlib.crc32 of a stable encoding instead"
    ),
    "FLC003": (
        "host-sync construct on a traced value inside jit-reachable code "
        "(float()/int()/bool()/.item()/np.asarray); keep host conversions "
        "outside the traced region"
    ),
    "FLC004": (
        "Python int arithmetic crosses the jnp boundary without an "
        "explicit dtype — host ints above 2**31-1 silently overflow the "
        "default int32; pass dtype="
    ),
    "FLC005": (
        "catastrophic cancellation: log(1 + x) / 1 - exp(x) lose all "
        "precision for small |x|; use log1p(x) / expm1(x)"
    ),
    "FLC006": (
        "pinned error message duplicated as a literal; import the "
        "constant from repro.core.errors instead"
    ),
    "FLC007": (
        "hypothesis/zstandard imported outside the try/except "
        "optional-dependency shim (offline CI has neither wheel)"
    ),
    "FLC008": (
        "host span or counter inside jit-reachable code runs once per "
        "trace, not per call; move it to the host code around the jitted "
        "call"
    ),
}

_SUPPRESS_RE = re.compile(
    r"#\s*flcheck:\s*disable(?:=(?P<rules>[A-Z0-9,\s]+))?"
)

# `from A import B` pairs known to bind a *module* even though the checker
# cannot see A's files (external packages); first-party repro.* modules are
# resolved against the filesystem instead.
_KNOWN_MODULE_FROMS = {
    ("jax", "numpy"), ("jax", "lax"), ("jax", "random"), ("jax", "nn"),
    ("jax", "tree_util"), ("jax", "monitoring"), ("jax", "sharding"),
    ("jax", "experimental"), ("jax.experimental", "pallas"),
    ("jax", "scipy"), ("numpy", "random"), ("numpy", "linalg"),
}

# Call targets whose function-valued arguments enter traced execution.
_TRACING_TRANSFORMS = {
    "jax.jit", "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.map", "jax.lax.switch",
    "jax.experimental.shard_map.shard_map", "jax.shard_map",
}

_JIT_WRAPPERS = {"jax.jit", "jax.vmap", "jax.pmap"}          # FLC001
_HOST_CASTS = {"float", "int", "bool"}                        # FLC003
_OPTIONAL_DEPS = {"hypothesis", "zstandard"}                  # FLC007
_LOG_FUNCS = {"jax.numpy.log", "numpy.log", "math.log"}       # FLC005
_EXP_FUNCS = {"jax.numpy.exp", "numpy.exp", "math.exp"}       # FLC005
_JNP_CTORS = {"jax.numpy.asarray", "jax.numpy.array"}         # FLC004
_SPAN_CALLS = {                                               # FLC008
    "repro.utils.spans.span", "repro.utils.spans.count",
    "jax.profiler.TraceAnnotation",
}


@dataclasses.dataclass(frozen=True)
class Diagnostic:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self):
        return f"{self.path}:{self.line} {self.rule} {self.message}"


# --------------------------------------------------------------------------
# FLC006 signatures: parse repro/core/errors.py, never import it
# --------------------------------------------------------------------------

_PLACEHOLDER_RE = re.compile(r"\{[^{}]*\}")
_MIN_FRAGMENT = 24   # short literal runs ("unknown uplink ") are too generic


def pinned_fragments(errors_path: str) -> dict:
    """``{fragment: constant_name}`` from the error-constants module.

    Each UPPER_CASE string constant contributes its longest
    placeholder-free run (>= ``_MIN_FRAGMENT`` chars) as the duplication
    signature FLC006 greps literals for.
    """
    with open(errors_path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=errors_path)
    frags = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (isinstance(tgt, ast.Name) and tgt.id.isupper()):
            continue
        if not (isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            continue
        runs = [r.strip() for r in _PLACEHOLDER_RE.split(node.value.value)]
        runs = [r for r in runs if len(r) >= _MIN_FRAGMENT]
        if runs:
            frags[max(runs, key=len)] = tgt.id
    return frags


def find_errors_module(search_dirs) -> str | None:
    """Locate ``repro/core/errors.py`` under the given directories."""
    for d in search_dirs:
        cand = os.path.join(d, "repro", "core", "errors.py")
        if os.path.isfile(cand):
            return cand
    return None


# --------------------------------------------------------------------------
# Per-file context: imports, module aliases, dotted-name resolution
# --------------------------------------------------------------------------

class _FileContext:
    def __init__(self, path: str, search_dirs):
        self.path = path
        self.search_dirs = list(search_dirs)
        self.alias_to_module: dict = {}   # name -> dotted module path
        self.from_imports: dict = {}      # name -> (module, original name)

    # -- import collection ---------------------------------------------------

    def collect_imports(self, tree: ast.AST) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name.split(".")[0]
                    self.alias_to_module[name] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = (
                        node.module, a.name
                    )

    # -- module-ness ---------------------------------------------------------

    def _from_import_is_module(self, module: str, name: str) -> bool:
        if (module, name) in _KNOWN_MODULE_FROMS:
            return True
        rel = os.path.join(*module.split("."), name)
        for d in self.search_dirs:
            p = os.path.join(d, rel)
            if os.path.isdir(p) or os.path.isfile(p + ".py"):
                return True
        return False

    def is_module_name(self, name: str) -> bool:
        if name in self.alias_to_module:
            return True
        if name in self.from_imports:
            return self._from_import_is_module(*self.from_imports[name])
        return False

    # -- dotted resolution ---------------------------------------------------

    def resolve(self, node: ast.AST) -> str | None:
        """Dotted path of a Name/Attribute chain with aliases expanded.

        ``jnp.log`` -> ``jax.numpy.log``; ``jit`` (from jax import jit) ->
        ``jax.jit``; unresolvable bases return None.
        """
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        base = node.id
        if base in self.alias_to_module:
            head = self.alias_to_module[base]
        elif base in self.from_imports:
            mod, orig = self.from_imports[base]
            head = f"{mod}.{orig}"
        else:
            head = base
        return ".".join([head] + list(reversed(parts)))

    def module_key(self) -> str:
        """Dotted module name of this file, relative to a search dir."""
        p = os.path.normpath(self.path)
        for d in self.search_dirs:
            d = os.path.normpath(d)
            if p.startswith(d + os.sep):
                rel = p[len(d) + 1:]
                break
        else:
            rel = p
        rel = rel[:-3] if rel.endswith(".py") else rel
        parts = rel.split(os.sep)
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)


# --------------------------------------------------------------------------
# Function table for the FLC003 call graph
# --------------------------------------------------------------------------

@dataclasses.dataclass
class _FuncInfo:
    key: tuple                 # (module_key, name)
    path: str
    is_root: bool = False
    calls: set = dataclasses.field(default_factory=set)    # callee keys
    # (line, rule, desc): FLC003/FLC008 sites, kept if jit-reachable
    candidates: list = dataclasses.field(default_factory=list)


def _contains_traced_call(node: ast.AST, ctx: _FileContext,
                          traced_names: set) -> bool:
    """Positive evidence the expression holds a traced value: a call into
    jax.* / jax.numpy.*, or a name previously assigned from one."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            dotted = ctx.resolve(sub.func)
            if dotted and (dotted.startswith("jax.") or dotted == "jax"):
                return True
        elif isinstance(sub, ast.Name) and sub.id in traced_names:
            return True
    return False


def _is_static_safe(node: ast.AST) -> bool:
    """Shape-/len-derived expressions are host ints even under tracing."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in (
            "shape", "ndim", "size", "dtype",
        ):
            return True
        if (isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name)
                and sub.func.id == "len"):
            return True
    return False


# --------------------------------------------------------------------------
# The per-file visitor
# --------------------------------------------------------------------------

class _Visitor(ast.NodeVisitor):
    def __init__(self, ctx: _FileContext, fragments: dict,
                 is_errors_module: bool):
        self.ctx = ctx
        self.fragments = fragments
        self.is_errors_module = is_errors_module
        self.diags: list = []        # raw (line, rule) pre-suppression
        self.funcs: dict = {}        # name -> _FuncInfo (module scope, nested flat)
        self._func_stack: list = []  # _FuncInfo currently being visited
        self._traced_stack: list = []  # per-function traced-name sets
        self._try_import_depth = 0   # inside try: ... except ImportError
        self._lambda_roots = 0

    # -- helpers -------------------------------------------------------------

    def _emit(self, node: ast.AST, rule: str) -> None:
        self.diags.append((node.lineno, rule))

    def _fn_key(self, name: str) -> tuple:
        return (self.ctx.module_key(), name)

    def _current(self) -> "_FuncInfo | None":
        return self._func_stack[-1] if self._func_stack else None

    def _resolve_callee_key(self, func: ast.AST) -> "tuple | None":
        """(module, name) of a called function, for call-graph edges."""
        if isinstance(func, ast.Name):
            name = func.id
            if name in self.ctx.from_imports:
                mod, orig = self.ctx.from_imports[name]
                return (mod, orig)
            return self._fn_key(name)
        if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
            base = func.value.id
            if base in self.ctx.alias_to_module:
                return (self.ctx.alias_to_module[base], func.attr)
            if base in self.ctx.from_imports:
                mod, orig = self.ctx.from_imports[base]
                return (f"{mod}.{orig}", func.attr)
        return None

    def _decorated_as_root(self, node) -> bool:
        for dec in node.decorator_list:
            for sub in ast.walk(dec):
                if isinstance(sub, (ast.Name, ast.Attribute)):
                    dotted = self.ctx.resolve(sub)
                    if dotted in _TRACING_TRANSFORMS:
                        return True
        return False

    # -- imports (FLC007) ----------------------------------------------------

    def _check_optional_import(self, node, modname: str) -> None:
        root = (modname or "").split(".")[0]
        if root in _OPTIONAL_DEPS and self._try_import_depth == 0:
            self._emit(node, "FLC007")

    def visit_Import(self, node):
        for a in node.names:
            self._check_optional_import(node, a.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        self._check_optional_import(node, node.module or "")
        self.generic_visit(node)

    def visit_Try(self, node):
        catches_import = any(
            h.type is not None and any(
                isinstance(n, (ast.Name, ast.Attribute))
                and (getattr(n, "id", None) or getattr(n, "attr", None)) in (
                    "ImportError", "ModuleNotFoundError", "Exception",
                )
                for n in ast.walk(h.type)
            )
            for h in node.handlers
        )
        if catches_import:
            self._try_import_depth += 1
            for stmt in node.body:
                self.visit(stmt)
            self._try_import_depth -= 1
            for part in (node.handlers, node.orelse, node.finalbody):
                for stmt in part:
                    self.visit(stmt)
        else:
            self.generic_visit(node)

    # -- function scopes -----------------------------------------------------

    def _visit_function(self, node, name: str):
        info = self.funcs.setdefault(
            self._fn_key(name), _FuncInfo(self._fn_key(name), self.ctx.path)
        )
        if self._decorated_as_root(node):
            info.is_root = True
        # params with scalar/None defaults are config statics, not traced
        traced: set = set()
        self._func_stack.append(info)
        self._traced_stack.append(traced)
        self.generic_visit(node)
        self._traced_stack.pop()
        self._func_stack.pop()

    def visit_FunctionDef(self, node):
        self._visit_function(node, node.name)

    def visit_AsyncFunctionDef(self, node):
        self._visit_function(node, node.name)

    def visit_Lambda(self, node):
        # lambda bodies share the enclosing function's traced-name context
        self.generic_visit(node)

    # -- assignments: positive-evidence tracking for FLC003 ------------------

    def _mark_assigned(self, target, value) -> None:
        if not self._traced_stack:
            return
        if not _contains_traced_call(value, self.ctx, self._traced_stack[-1]):
            return
        names = []
        if isinstance(target, ast.Name):
            names = [target.id]
        elif isinstance(target, (ast.Tuple, ast.List)):
            names = [e.id for e in target.elts if isinstance(e, ast.Name)]
        self._traced_stack[-1].update(names)

    def visit_Assign(self, node):
        for tgt in node.targets:
            self._mark_assigned(tgt, node.value)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._mark_assigned(node.target, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        if node.value is not None:
            self._mark_assigned(node.target, node.value)
        self.generic_visit(node)

    # -- raise (FLC006) ------------------------------------------------------

    def visit_Raise(self, node):
        if not self.is_errors_module and self.fragments and node.exc:
            exc = node.exc
            if isinstance(exc, ast.Call) and exc.args:
                text = _literal_text(exc.args[0])
                if text and any(f in text for f in self.fragments):
                    self._emit(node, "FLC006")
        self.generic_visit(node)

    # -- binops (FLC005: 1 - exp(x)) -----------------------------------------

    def visit_BinOp(self, node):
        if isinstance(node.op, ast.Sub) and _is_const_one(node.left):
            right = node.right
            if isinstance(right, ast.Call):
                dotted = self.ctx.resolve(right.func)
                if dotted in _EXP_FUNCS:
                    self._emit(node, "FLC005")
        self.generic_visit(node)

    # -- calls: FLC001/002/003/004/005 + call graph --------------------------

    def visit_Call(self, node):
        ctx = self.ctx
        dotted = ctx.resolve(node.func)
        cur = self._current()

        # call-graph edge
        if cur is not None:
            callee = self._resolve_callee_key(node.func)
            if callee is not None:
                cur.calls.add(callee)

        # FLC001: jit/vmap/pmap of bound method / lambda at call time
        if dotted in _JIT_WRAPPERS and node.args and cur is not None:
            first = node.args[0]
            if isinstance(first, ast.Lambda):
                self._emit(node, "FLC001")
            elif isinstance(first, ast.Attribute):
                base = first.value
                while isinstance(base, ast.Attribute):
                    base = base.value
                if not (isinstance(base, ast.Name)
                        and ctx.is_module_name(base.id)):
                    self._emit(node, "FLC001")

        # FLC002: builtin hash()/id()
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("hash", "id")
                and node.func.id not in ctx.from_imports
                and node.func.id not in ctx.alias_to_module):
            self._emit(node, "FLC002")

        # FLC004: jnp.asarray/array of host int arithmetic, no dtype
        if dotted in _JNP_CTORS and node.args:
            first = node.args[0]
            has_dtype = len(node.args) >= 2 or any(
                kw.arg == "dtype" for kw in node.keywords
            )
            if (isinstance(first, ast.BinOp) and not has_dtype
                    and not _contains_traced_call(first, ctx, set())
                    and not _is_static_safe(first)):
                self._emit(node, "FLC004")

        # FLC005: log(1 + x)
        if dotted in _LOG_FUNCS and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
                if _is_const_one(arg.left) or _is_const_one(arg.right):
                    self._emit(node, "FLC005")

        # FLC003/FLC008 candidates (validated against jit-reachability later)
        if cur is not None:
            traced = self._traced_stack[-1] if self._traced_stack else set()
            if (isinstance(node.func, ast.Name)
                    and node.func.id in _HOST_CASTS and node.args
                    and not _is_static_safe(node.args[0])
                    and _contains_traced_call(node.args[0], ctx, traced)):
                cur.candidates.append((node.lineno, "FLC003", node.func.id))
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                cur.candidates.append((node.lineno, "FLC003", ".item()"))
            elif (dotted in ("numpy.asarray", "numpy.array") and node.args
                    and not _is_static_safe(node.args[0])
                    and _contains_traced_call(node.args[0], ctx, traced)):
                cur.candidates.append((node.lineno, "FLC003", "np.asarray"))
            elif dotted in _SPAN_CALLS:
                cur.candidates.append((node.lineno, "FLC008", dotted))

        # transform calls: function-valued args become FLC003 roots
        if dotted in _TRACING_TRANSFORMS:
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    key = self._resolve_callee_key(arg)
                    root = self.funcs.setdefault(
                        key, _FuncInfo(key, ctx.path)
                    )
                    root.is_root = True
                elif isinstance(arg, ast.Lambda):
                    self._lambda_roots += 1
                    key = self._fn_key(f"<lambda-root:{node.lineno}:"
                                       f"{self._lambda_roots}>")
                    info = _FuncInfo(key, ctx.path, is_root=True)
                    self.funcs[key] = info
                    self._func_stack.append(info)
                    self._traced_stack.append(
                        set(self._traced_stack[-1])
                        if self._traced_stack else set()
                    )
                    self.visit(arg.body)
                    self._traced_stack.pop()
                    self._func_stack.pop()

        self.generic_visit(node)


def _is_const_one(node: ast.AST) -> bool:
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, (int, float))
            and node.value == 1)


def _literal_text(node: ast.AST) -> "str | None":
    """Literal text of a str Constant or the str parts of an f-string."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        return "".join(
            v.value for v in node.values
            if isinstance(v, ast.Constant) and isinstance(v.value, str)
        )
    return None


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------

def _suppressed_rules(lines, lineno: int, end_lineno: int) -> set:
    out: set = set()
    for ln in range(lineno, min(end_lineno, len(lines)) + 1):
        m = _SUPPRESS_RE.search(lines[ln - 1])
        if m:
            named = m.group("rules")
            if named is None:
                out.add("*")
            else:
                out.update(r.strip() for r in named.split(","))
    return out


@dataclasses.dataclass
class FileResult:
    path: str
    diags: list                 # Diagnostic (local rules, suppression applied)
    funcs: dict                 # (module, name) -> _FuncInfo
    lines: list


def check_file(path: str, *, search_dirs=("src", "."),
               fragments: "dict | None" = None) -> FileResult:
    with open(path, encoding="utf-8") as fh:
        source = fh.read()
    lines = source.splitlines()
    tree = ast.parse(source, filename=path)
    ctx = _FileContext(path, search_dirs)
    ctx.collect_imports(tree)
    is_errors_module = os.path.normpath(path).endswith(
        os.path.join("repro", "core", "errors.py")
    )
    visitor = _Visitor(ctx, fragments or {}, is_errors_module)
    visitor.visit(tree)

    diags = []
    # sorted(set(...)): lambda bodies handed to transforms are walked twice
    # (as a synthetic root and via generic_visit) — never report twice
    for line, rule in sorted(set(visitor.diags)):
        sup = _suppressed_rules(lines, line, line)
        if "*" in sup or rule in sup:
            continue
        diags.append(Diagnostic(path, line, rule, RULES[rule]))
    return FileResult(path, diags, visitor.funcs, lines)


def _reachable(funcs: dict) -> set:
    roots = [k for k, f in funcs.items() if f.is_root]
    seen = set(roots)
    work = list(roots)
    while work:
        key = work.pop()
        info = funcs.get(key)
        if info is None:
            continue
        for callee in info.calls:
            if callee not in seen and callee in funcs:
                seen.add(callee)
                work.append(callee)
    return seen


def check_paths(paths, *, search_dirs=("src", "."),
                fragments: "dict | None" = None) -> list:
    """Run all rules over the given files/directories; returns Diagnostics.

    Local rules apply per file; FLC003 and FLC008 resolve jit-reachability
    over the union call graph of every scanned file, so cross-module
    reachability (driver in one module, traced helper in another) is
    honored.
    """
    files = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d not in ("__pycache__", ".git", "corpus")
                )
                files.extend(
                    os.path.join(dirpath, f)
                    for f in sorted(filenames) if f.endswith(".py")
                )
        elif p.endswith(".py"):
            files.append(p)

    results = [
        check_file(f, search_dirs=search_dirs, fragments=fragments)
        for f in files
    ]

    funcs: dict = {}
    for res in results:
        for key, info in res.funcs.items():
            if key in funcs:
                merged = funcs[key]
                merged.is_root = merged.is_root or info.is_root
                merged.calls |= info.calls
                merged.candidates.extend(
                    (*c, info.path) for c in info.candidates
                )
            else:
                info.candidates = [(*c, info.path) for c in info.candidates]
                funcs[key] = info

    reach = _reachable(funcs)
    lines_of = {res.path: res.lines for res in results}
    diags = [d for res in results for d in res.diags]
    for key in sorted(reach):
        info = funcs.get(key)
        if info is None:
            continue
        for ln, rule, desc, path in info.candidates:
            sup = _suppressed_rules(lines_of.get(path, []), ln, ln)
            if "*" in sup or rule in sup:
                continue
            diags.append(Diagnostic(
                path, ln, rule, f"{RULES[rule]} [{desc}]"
            ))
    return sorted(set(diags), key=lambda d: (d.path, d.line, d.rule))
