"""dtmlint AST rules — project invariants as ruff-style checks.

Every rule exists because a past PR fixed (or nearly shipped) the bug
class by hand; the rationale on each rule names the incident.  Rules are
scoped by path inside ``src/`` (a rule about Pallas kernels only fires
under ``repro/kernels/``), findings carry ``CODE line:col message``, and
any finding can be suppressed by putting ``# dtmlint: disable=DTMxxx``
(comma-separated codes, or ``all``) on the flagged line.

Generic Python hygiene (unused imports, undefined names, style) is
ruff's job — see ``[tool.ruff]`` in pyproject.toml.  dtmlint only checks
things ruff cannot know about this codebase.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Callable, List, Optional, Sequence

__all__ = ["RULES", "Finding", "lint_source", "lint_paths", "main"]


# --------------------------------------------------------------------------- #
# rule table                                                                  #
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Rule:
    code: str
    name: str
    scope: str          # human-readable path scope
    rationale: str      # which PR / incident motivated it


RULES: Sequence[Rule] = (
    Rule("DTM001", "unsized-dynamic-shape",
         "src/ (all)",
         "PR 5: jnp.nonzero/flatnonzero/argwhere (and one-arg jnp.where) "
         "without size= produce data-dependent shapes — every distinct "
         "count retraces, unbounded jit caches.  The compacted TA-update "
         "path only stayed at cache==1 because of size=k/fill_value."),
    Rule("DTM002", "env-read-outside-resolver",
         "src/ except kernels/ops.py, kernels/autotune.py",
         "PR 1: REPRO_* knobs are resolved ONCE in kernels/ops.py (and "
         "the autotune cache in kernels/autotune.py).  A stray os.environ "
         "read elsewhere re-decides config mid-run — the class of bug "
         "behind PR 3's silent packed_vpu→mxu fallback."),
    Rule("DTM003", "hot-path-sync",
         "src/repro/launch/",
         "PR 7: the async scheduler keeps pipeline_depth launches in "
         "flight; any block_until_ready outside collect() re-serialises "
         "the device and silently erases the continuous-batching win."),
    Rule("DTM004", "python-branch-on-traced",
         "src/repro/kernels/, core/dtm.py, core/feedback.py",
         "Traced-module invariant: Python if/while on a jnp/lax value "
         "concretises the tracer (ConcretizationTypeError at best, a "
         "silent host sync + retrace at worst).  Use jnp.where/lax.cond."),
    Rule("DTM005", "untyped-int-literal-array",
         "src/repro/kernels/, core/dtm.py",
         "PR 3: the canonical datapath is uint8 TA states + uint32 packed "
         "literals.  jnp.asarray(0)/jnp.full(s, 1) without dtype "
         "materialise int32 and silently promote the packed operands "
         "back to wide ints — spell the dtype."),
    Rule("DTM006", "writeable-lru-cached-array",
         "src/ (all)",
         "PR 4: an lru_cache'd numpy array escaped writeable; one caller "
         "mutating it corrupted every later cache hit.  Cached arrays "
         "must set .flags.writeable = False before returning."),
    Rule("DTM007", "mutable-default-arg",
         "src/ (all)",
         "Generic footgun with project teeth: a mutable default on an "
         "engine/server entry point is shared across tenants."),
    Rule("DTM008", "interpret-literal-default",
         "src/repro/kernels/",
         "PR 5: packed_clause_eval defaulted interpret=True, so direct "
         "callers on TPU ran the interpreted kernel — silently, at "
         "~100x.  Kernel entry points must default interpret=None and "
         "resolve through ops.resolve_interpret()."),
    Rule("DTM009", "bare-except",
         "src/ (all)",
         "PR 3 + PR 8: both silent-fallback bugs (packed_vpu→mxu, "
         "prng_backend typo) were swallow-and-continue shapes.  Catch "
         "something nameable or let it raise."),
    Rule("DTM010", "unlocked-stats-read",
         "src/repro/launch/scheduler.py",
         "PR 7 added stats() surfaces without auditing lock coverage: "
         "counters and _in_flight were read outside self._work while the "
         "driver thread mutates them.  Every self.* read in stats() "
         "belongs under the condition."),
    Rule("DTM011", "non-atomic-file-publish",
         "src/repro/checkpoint/, src/repro/runtime/",
         "PR 10: durable state must publish atomically (write to a tmp "
         "path, then os.replace) — checkpoint.py's TOCTOU finalize and a "
         "crash between open(final, 'w') and json.dump leave a torn file "
         "a reader then trusts.  Writes in the durability layer go "
         "through a *tmp* path."),
)

_RULES_BY_CODE = {r.code: r for r in RULES}

_ENV_OK = ("repro/kernels/ops.py", "repro/kernels/autotune.py")
_TRACED_MODULES = ("repro/core/dtm.py", "repro/core/feedback.py")
_PACKED_MODULES = ("repro/core/dtm.py",)


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    code: str
    message: str

    def render(self, explain: bool = False) -> str:
        s = f"{self.path}:{self.line}:{self.col}: {self.code} {self.message}"
        if explain:
            s += f"\n    why: {_RULES_BY_CODE[self.code].rationale}"
        return s


# --------------------------------------------------------------------------- #
# helpers                                                                     #
# --------------------------------------------------------------------------- #

def _norm(path: str) -> str:
    """Posix path from the ``repro/`` package root (fixture-friendly)."""
    p = Path(path).as_posix()
    i = p.rfind("repro/")
    return p[i:] if i >= 0 else p


def _root_name(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an attribute chain: jnp.foo.bar -> 'jnp'."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_jnp_call(node: ast.Call, attrs: set) -> bool:
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr in attrs
            and _root_name(f) in ("jnp", "numpy_like", "jax"))


def _kw(node: ast.Call, name: str) -> bool:
    return any(k.arg == name for k in node.keywords)


_SUPPRESS_RE = re.compile(r"#\s*dtmlint:\s*disable=([A-Za-z0-9_,\s]+|all)")


def _suppressed(lines: Sequence[str], f: Finding) -> bool:
    if not (1 <= f.line <= len(lines)):
        return False
    m = _SUPPRESS_RE.search(lines[f.line - 1])
    if not m:
        return False
    spec = m.group(1).strip()
    if spec == "all":
        return True
    return f.code in {c.strip() for c in spec.split(",")}


# --------------------------------------------------------------------------- #
# the visitor                                                                 #
# --------------------------------------------------------------------------- #

class _Visitor(ast.NodeVisitor):
    def __init__(self, relpath: str):
        self.path = relpath
        self.findings: List[Finding] = []
        self._fn_stack: List[str] = []
        self._lock_depth = 0        # inside `with self._work:` (DTM010)

        self.in_kernels = "repro/kernels/" in relpath
        self.in_launch = "repro/launch/" in relpath
        self.in_traced = (self.in_kernels
                          or any(relpath.endswith(m)
                                 for m in _TRACED_MODULES))
        self.in_packed = (self.in_kernels
                          or any(relpath.endswith(m)
                                 for m in _PACKED_MODULES))
        self.env_ok = any(relpath.endswith(m) for m in _ENV_OK)
        self.in_scheduler = relpath.endswith("repro/launch/scheduler.py")
        self.in_durable = ("repro/checkpoint/" in relpath
                           or "repro/runtime/" in relpath)

    def _flag(self, node: ast.AST, code: str, message: str) -> None:
        self.findings.append(Finding(self.path, node.lineno,
                                     node.col_offset, code, message))

    # ---- function defs: DTM006 / DTM007 / DTM008 / DTM010 scope ----------
    def _visit_fn(self, node) -> None:
        self._check_mutable_defaults(node)
        self._check_lru_cache(node)
        self._check_interpret_default(node)
        self._fn_stack.append(node.name)
        if self.in_scheduler and node.name == "stats":
            self._check_stats_locking(node)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _check_mutable_defaults(self, node) -> None:
        for d in list(node.args.defaults) + [d for d in
                                             node.args.kw_defaults if d]:
            bad = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call) and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set", "bytearray"))
            if bad:
                self._flag(d, "DTM007",
                           f"mutable default argument in {node.name}() — "
                           "use None and construct inside")

    def _check_lru_cache(self, node) -> None:
        cached = False
        for dec in node.decorator_list:
            tgt = dec.func if isinstance(dec, ast.Call) else dec
            name = tgt.attr if isinstance(tgt, ast.Attribute) else (
                tgt.id if isinstance(tgt, ast.Name) else None)
            if name in ("lru_cache", "cache"):
                cached = True
        if not cached:
            return
        makes_array, freezes = False, False
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and _root_name(sub.func) in ("np", "numpy")):
                makes_array = True
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    if (isinstance(t, ast.Attribute)
                            and t.attr == "writeable"
                            and isinstance(t.value, ast.Attribute)
                            and t.value.attr == "flags"):
                        freezes = True
        if makes_array and not freezes:
            self._flag(node, "DTM006",
                       f"lru_cache'd {node.name}() builds numpy arrays "
                       "without .flags.writeable = False — cache hits "
                       "share a mutable buffer")

    def _check_interpret_default(self, node) -> None:
        if not self.in_kernels:
            return
        args = list(node.args.args) + list(node.args.kwonlyargs)
        defaults = ([None] * (len(node.args.args)
                              - len(node.args.defaults))
                    + list(node.args.defaults)
                    + list(node.args.kw_defaults))
        for a, d in zip(args, defaults):
            if (a.arg == "interpret" and isinstance(d, ast.Constant)
                    and isinstance(d.value, bool)):
                self._flag(d, "DTM008",
                           f"{node.name}() defaults interpret="
                           f"{d.value} — default to None and resolve "
                           "via ops.resolve_interpret()")

    # ---- DTM010: every self.* read in stats() under the lock --------------
    def _check_stats_locking(self, node) -> None:
        def scan(n: ast.AST, locked: bool) -> None:
            if isinstance(n, ast.With):
                takes = any(
                    isinstance(i.context_expr, ast.Attribute)
                    and i.context_expr.attr == "_work"
                    and isinstance(i.context_expr.value, ast.Name)
                    and i.context_expr.value.id == "self"
                    for i in n.items)
                for c in ast.iter_child_nodes(n):
                    scan(c, locked or takes)
                return
            if (isinstance(n, ast.Attribute)
                    and isinstance(n.value, ast.Name)
                    and n.value.id == "self" and n.attr != "_work"
                    and not locked):
                self._flag(n, "DTM010",
                           f"stats() reads self.{n.attr} outside "
                           "`with self._work` — snapshot under the lock")
            for c in ast.iter_child_nodes(n):
                scan(c, locked)

        for stmt in node.body:
            scan(stmt, False)

    # ---- calls: DTM001 / DTM002 / DTM003 ---------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        if (_is_jnp_call(node, {"nonzero", "flatnonzero", "argwhere"})
                and not _kw(node, "size")):
            self._flag(node, "DTM001",
                       f"jnp.{node.func.attr} without size= — "
                       "data-dependent shape retraces per distinct count")
        if (_is_jnp_call(node, {"where"}) and len(node.args) == 1
                and not _kw(node, "size")):
            self._flag(node, "DTM001",
                       "one-arg jnp.where without size= — "
                       "data-dependent shape retraces per distinct count")
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr == "getenv"
                and _root_name(f) == "os" and not self.env_ok):
            self._flag(node, "DTM002",
                       "os.getenv outside kernels/ops.py|autotune.py — "
                       "config resolves once in the designated sites")
        if (isinstance(f, ast.Attribute) and f.attr == "block_until_ready"
                and self.in_launch and "collect" not in self._fn_stack):
            self._flag(node, "DTM003",
                       "block_until_ready under launch/ outside collect() "
                       "— serialises the async pipeline")
        self._check_atomic_publish(node)
        self.generic_visit(node)

    # ---- DTM011: durable writes must go through a tmp path ----------------
    @staticmethod
    def _path_mentions_tmp(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            name = (sub.id if isinstance(sub, ast.Name) else
                    sub.attr if isinstance(sub, ast.Attribute) else
                    sub.value if (isinstance(sub, ast.Constant)
                                  and isinstance(sub.value, str)) else None)
            if name is not None and "tmp" in name.lower():
                return True
        return False

    def _check_atomic_publish(self, node: ast.Call) -> None:
        if not self.in_durable:
            return
        f = node.func
        if (isinstance(f, ast.Name) and f.id == "open"
                and len(node.args) >= 2):
            mode = node.args[1]
            if (isinstance(mode, ast.Constant)
                    and isinstance(mode.value, str)
                    and any(c in mode.value for c in "wax")
                    and not self._path_mentions_tmp(node.args[0])):
                self._flag(node, "DTM011",
                           "file written at its final path — write to a "
                           "*tmp* path and os.replace (atomic publish)")
        if (isinstance(f, ast.Attribute)
                and f.attr in ("save", "savez", "savez_compressed")
                and _root_name(f) in ("np", "numpy") and node.args
                and not self._path_mentions_tmp(node.args[0])):
            self._flag(node, "DTM011",
                       f"np.{f.attr} to a final path — write under a "
                       "*tmp* dir and os.replace (atomic publish)")

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if (node.attr == "environ" and isinstance(node.value, ast.Name)
                and node.value.id == "os" and not self.env_ok):
            self._flag(node, "DTM002",
                       "os.environ outside kernels/ops.py|autotune.py — "
                       "config resolves once in the designated sites")
        self.generic_visit(node)

    # ---- DTM004: Python control flow on traced values ---------------------
    def _check_branch(self, node) -> None:
        if not self.in_traced or not self._fn_stack:
            self.generic_visit(node)
            return
        for sub in ast.walk(node.test):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            if not isinstance(f, ast.Attribute):
                continue
            root = _root_name(f)
            traced = root in ("jnp", "lax") or (
                f.attr in ("any", "all", "item") and root not in
                ("np", "numpy"))
            if traced:
                kind = "if" if isinstance(node, ast.If) else "while"
                self._flag(node, "DTM004",
                           f"Python `{kind}` on a traced value "
                           f"({ast.unparse(sub)}) — use jnp.where/"
                           "lax.cond, or hoist to host")
                break
        self.generic_visit(node)

    visit_If = _check_branch
    visit_While = _check_branch

    # ---- DTM005: untyped int-literal materialisation ----------------------
    def _literal_payload(self, node: ast.Call) -> Optional[ast.Constant]:
        attr = node.func.attr
        if attr in ("asarray", "array") and len(node.args) == 1:
            c = node.args[0]
        elif attr == "full" and len(node.args) == 2:
            c = node.args[1]
        else:
            return None
        if (isinstance(c, ast.Constant) and isinstance(c.value, int)
                and not isinstance(c.value, bool)):
            return c
        return None

    def visit_Expr(self, node):           # keep traversal default
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self._flag(node, "DTM009",
                       "bare except — silent fallbacks hid the "
                       "packed_vpu and prng_backend bugs; name the "
                       "exception")
        self.generic_visit(node)


class _PackedVisitor(ast.NodeVisitor):
    """Second pass for DTM005 (separate so visit_Call stays readable)."""

    def __init__(self, outer: _Visitor):
        self.o = outer

    def visit_Call(self, node: ast.Call) -> None:
        if (self.o.in_packed
                and _is_jnp_call(node, {"asarray", "array", "full"})
                and not _kw(node, "dtype")):
            c = self.o._literal_payload(node)
            if c is not None:
                self.o._flag(
                    node, "DTM005",
                    f"jnp.{node.func.attr}({c.value}) without dtype "
                    "materialises int32 against the uint8/uint32 packed "
                    "layout — spell the dtype")
        self.generic_visit(node)


# --------------------------------------------------------------------------- #
# entry points                                                                #
# --------------------------------------------------------------------------- #

def lint_source(src: str, relpath: str) -> List[Finding]:
    """Lint one source string as if it lived at ``relpath``."""
    tree = ast.parse(src, filename=relpath)
    v = _Visitor(_norm(relpath))
    v.visit(tree)
    _PackedVisitor(v).visit(tree)
    lines = src.splitlines()
    out = [f for f in v.findings if not _suppressed(lines, f)]
    out.sort(key=lambda f: (f.line, f.col, f.code))
    return out


def lint_paths(paths: Sequence[str],
               progress: Optional[Callable[[str], None]] = None
               ) -> List[Finding]:
    """Lint files and directories (recursively, ``*.py``)."""
    files: List[Path] = []
    for p in map(Path, paths):
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        else:
            files.append(p)
    findings: List[Finding] = []
    for f in files:
        if progress:
            progress(str(f))
        findings.extend(lint_source(f.read_text(), str(f)))
    return findings


def main(argv: Sequence[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="dtmlint lint", description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+")
    ap.add_argument("--explain", action="store_true",
                    help="print each rule's motivating rationale")
    ap.add_argument("--rules", action="store_true",
                    help="print the rule table and exit")
    ns = ap.parse_args(list(argv))
    if ns.rules:
        for r in RULES:
            print(f"{r.code} {r.name:28s} [{r.scope}]")
            print(f"    {r.rationale}")
        return 0
    findings = lint_paths(ns.paths)
    for f in findings:
        print(f.render(explain=ns.explain))
    print(f"dtmlint: {len(findings)} finding(s), "
          f"{len(RULES)} rules active")
    return 1 if findings else 0
