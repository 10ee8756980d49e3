"""Core of the ``repro.analysis`` lint engine.

Pure-stdlib (no jax import): the analyzer must run anywhere — CI lint jobs,
pre-commit hooks, containers without an accelerator stack.  The engine
parses each file once, builds a :class:`ModuleIndex` (import aliases,
function table, jit/trace reachability), collects a project-wide
:class:`ProjectContext` (declared mesh axis names, donating callables), and
hands both to every registered :class:`Rule`.

Findings carry a *fingerprint* — a content hash of (rule, relative path,
normalized source line) — so the baseline survives unrelated line drift.

Suppressions: ``# repro: allow[rule-id]`` (comma-separated ids, or ``*``)
on the finding's line or the line directly above it.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------

SUPPRESS_RE = re.compile(r"#\s*repro:\s*allow\[([\w\-*, ]+)\]")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str           # as given on the command line (relative preferred)
    line: int
    col: int
    message: str

    @property
    def fingerprint(self) -> str:
        return finding_fingerprint(self.rule, self.path, self.line)

    def to_json(self) -> Dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message,
                "fingerprint": self.fingerprint}

    def format(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule} {self.message}")


_SOURCE_CACHE: Dict[str, List[str]] = {}


def _source_lines(path: str) -> List[str]:
    if path not in _SOURCE_CACHE:
        try:
            with open(path, encoding="utf-8") as f:
                _SOURCE_CACHE[path] = f.read().splitlines()
        except OSError:
            _SOURCE_CACHE[path] = []
    return _SOURCE_CACHE[path]


def finding_fingerprint(rule: str, path: str, line: int) -> str:
    """Content-addressed id: stable under line renumbering, invalidated when
    the flagged line itself changes."""
    lines = _source_lines(path)
    text = lines[line - 1].strip() if 0 < line <= len(lines) else ""
    rel = os.path.basename(path) if os.path.isabs(path) else path
    blob = f"{rule}:{rel}:{text}".encode()
    return hashlib.sha1(blob).hexdigest()[:16]


# ---------------------------------------------------------------------------
# import-alias resolution
# ---------------------------------------------------------------------------

class NameResolver:
    """Resolve an AST expression to its canonical dotted path.

    ``import jax.numpy as jnp`` + ``jnp.asarray`` -> ``jax.numpy.asarray``;
    ``from jax.lax import psum as P`` + ``P`` -> ``jax.lax.psum``.
    Unresolvable names resolve to themselves (first segment unaliased).
    """

    def __init__(self, tree: ast.Module):
        self.aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.aliases[a.asname or a.name] = (
                        f"{node.module}.{a.name}")

    def dotted(self, node: ast.AST) -> Optional[str]:
        """The raw dotted text of a Name/Attribute chain, else None."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        return None

    def canonical(self, node: ast.AST) -> Optional[str]:
        raw = self.dotted(node)
        if raw is None:
            return None
        head, _, rest = raw.partition(".")
        head = self.aliases.get(head, head)
        return f"{head}.{rest}" if rest else head


# canonical names that trace their function arguments (host python is
# staged out of these, so host syncs / tracer branches inside are bugs)
TRACING_ENTRY_CALLS = {
    "jax.jit", "jax.pmap", "jax.vmap",
    "jax.grad", "jax.value_and_grad", "jax.linearize", "jax.jacfwd",
    "jax.jacrev", "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.fori_loop", "jax.lax.while_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map", "jax.lax.associative_scan",
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "jax.experimental.pallas.pallas_call",
    "functools.partial",  # partial(jax.jit, ...)(f) handled via unwrap below
}

# decorators that make the decorated function a traced entry point
TRACING_DECORATORS = {
    "jax.jit", "jax.pmap", "jax.vmap", "jax.custom_vjp", "jax.custom_jvp",
    "jax.experimental.pallas.pallas_call",
    # repo-local: jit with donated (params, opt_state)
    "repro.core.trainer._jit_donated", "_jit_donated",
}


def _unwrap_partial(call: ast.Call, resolver: NameResolver) -> Optional[str]:
    """functools.partial(jax.jit, ...) -> 'jax.jit'."""
    fn = resolver.canonical(call.func)
    if fn == "functools.partial" and call.args:
        return resolver.canonical(call.args[0])
    return fn


class ModuleIndex:
    """Per-file facts shared by every rule: the AST, resolver, function
    table, and the set of functions reachable from a tracing entry point."""

    def __init__(self, path: str, tree: ast.Module, source: str):
        self.path = path
        self.tree = tree
        self.source = source
        self.resolver = NameResolver(tree)
        # function name -> def node (module-level and nested; nested names
        # shadow outer ones only within this simple map — fine for linting)
        self.functions: Dict[str, ast.AST] = {}
        self.parent: Dict[ast.AST, ast.AST] = {}
        # name -> Call it was last assigned from (partial-bound kernels) and
        # name -> Name/Attribute alias (`mk = make_mesh`)
        self.assigned_calls: Dict[str, ast.Call] = {}
        self.name_aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions.setdefault(node.name, node)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                tname = node.targets[0].id
                if isinstance(node.value, ast.Call):
                    self.assigned_calls[tname] = node.value
                elif isinstance(node.value, (ast.Name, ast.Attribute)):
                    alias = self.resolver.canonical(node.value)
                    if alias is not None:
                        self.name_aliases[tname] = alias
        self.traced: Set[ast.AST] = self._compute_traced()

    def canonical_callee(self, node: ast.AST) -> Optional[str]:
        """Canonical name of a callee, following one hop of module-level
        `alias = real_name` assignments."""
        canon = self.resolver.canonical(node)
        if canon is not None and "." not in canon:
            return self.name_aliases.get(canon, canon)
        return canon

    # -- traced-function reachability -----------------------------------
    def _entry_functions(self) -> Set[ast.AST]:
        entries: Set[ast.AST] = set()
        res = self.resolver
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    name = (res.canonical(dec.func)
                            if isinstance(dec, ast.Call) else
                            res.canonical(dec))
                    if isinstance(dec, ast.Call) and name == "functools.partial":
                        name = _unwrap_partial(dec, res)
                    if name in TRACING_DECORATORS or (
                            name is not None and name in TRACING_ENTRY_CALLS):
                        entries.add(node)
            elif isinstance(node, ast.Call):
                fn = _unwrap_partial(node, res)
                if fn in TRACING_ENTRY_CALLS and fn != "functools.partial":
                    for arg in list(node.args) + [kw.value
                                                  for kw in node.keywords]:
                        target = self._resolve_local_callable(arg)
                        if target is not None:
                            entries.add(target)
        return entries

    def _resolve_local_callable(self, node: ast.AST) -> Optional[ast.AST]:
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Name):
            if node.id in self.functions:
                return self.functions[node.id]
            # kernel = functools.partial(_kernel, ...) then pallas_call(kernel)
            bound = self.assigned_calls.get(node.id)
            if bound is not None:
                return self._resolve_local_callable(bound)
        if isinstance(node, ast.Call):
            # partial(body, ...) / wraps(body)(...) — take the first arg
            inner = self.resolver.canonical(node.func)
            if inner == "functools.partial" and node.args:
                return self._resolve_local_callable(node.args[0])
        return None

    def _compute_traced(self) -> Set[ast.AST]:
        traced: Set[ast.AST] = set()
        work = list(self._entry_functions())
        while work:
            fn = work.pop()
            if fn in traced:
                continue
            traced.add(fn)
            # every call to a locally-defined function from traced code is
            # traced too (conservative, module-local call graph)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    tgt = self._resolve_local_callable(node.func)
                    if tgt is not None and tgt not in traced:
                        work.append(tgt)
                    # function-valued args to lax.scan etc. nested inside
                    fnname = _unwrap_partial(node, self.resolver)
                    if fnname in TRACING_ENTRY_CALLS:
                        for arg in list(node.args) + [kw.value for kw in
                                                      node.keywords]:
                            t2 = self._resolve_local_callable(arg)
                            if t2 is not None and t2 not in traced:
                                work.append(t2)
        return traced

    def in_traced(self, node: ast.AST) -> bool:
        """Is ``node`` lexically inside a traced function?"""
        cur = node
        while cur is not None:
            if cur in self.traced:
                return True
            cur = self.parent.get(cur)
        return False

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = self.parent.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = self.parent.get(cur)
        return None


# ---------------------------------------------------------------------------
# project-wide context
# ---------------------------------------------------------------------------

MESH_CTORS = {"jax.sharding.Mesh", "jax.make_mesh",
              "jax.experimental.mesh_utils.create_device_mesh"}


@dataclasses.dataclass
class ProjectContext:
    """Facts that cross file boundaries (collected in a pre-pass over every
    analyzed file): the set of mesh axis names the project declares, and
    extra donating callables."""
    axis_names: Set[str] = dataclasses.field(default_factory=set)

    @classmethod
    def _literal_strs(cls, node: ast.AST) -> List[str]:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return [node.value]
        if isinstance(node, (ast.Tuple, ast.List)):
            out = []
            for el in node.elts:
                out.extend(cls._literal_strs(el))
            return out
        if isinstance(node, ast.IfExp):  # ("pod", "data") if multi else ...
            return cls._literal_strs(node.body) + cls._literal_strs(
                node.orelse)
        return []

    def collect(self, index: ModuleIndex) -> None:
        for node in ast.walk(index.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = index.canonical_callee(node.func)
            leaf = fn.split(".")[-1].lower() if fn is not None else ""
            # Mesh(devices, axis_names), jax.make_mesh(shape, names), and
            # repo factories (make_mesh/make_pipeline_mesh/...) all
            # put the axis-name tuple in the second positional slot
            if fn in MESH_CTORS or "mesh" in leaf:
                cands: List[ast.AST] = node.args[1:2]
                cands += [kw.value for kw in node.keywords
                          if kw.arg in ("axis_names", "axes")]
                for c in cands:
                    # axis tuples are often staged through a local var:
                    # `axes = ("pod", "data") if multi else ...; make_mesh(s, axes)`
                    if isinstance(c, ast.Name):
                        for n2 in ast.walk(index.tree):
                            if isinstance(n2, ast.Assign) and any(
                                    isinstance(t, ast.Name) and t.id == c.id
                                    for t in n2.targets):
                                self.axis_names.update(
                                    self._literal_strs(n2.value))
                    else:
                        self.axis_names.update(self._literal_strs(c))


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

class Rule:
    """A lint rule.  Subclasses set ``id``/``doc`` and implement ``check``
    yielding findings for one module."""

    id: str = ""
    doc: str = ""

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, index: ModuleIndex, node: ast.AST,
                message: str) -> Finding:
        return Finding(self.id, index.path, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0) + 1, message)


_REGISTRY: Dict[str, Rule] = {}


def register_rule(cls):
    """Class decorator adding a rule to the global registry."""
    inst = cls()
    if not inst.id:
        raise ValueError(f"rule {cls.__name__} has no id")
    if inst.id in _REGISTRY:
        raise ValueError(f"duplicate rule id {inst.id!r}")
    _REGISTRY[inst.id] = inst
    return cls


def all_rules() -> Dict[str, Rule]:
    # rules module registers on import; deferred to avoid a cycle
    from repro.analysis import rules as _rules  # noqa: F401
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# suppression + file runner
# ---------------------------------------------------------------------------

def suppressed_rules(lines: Sequence[str], lineno: int) -> Set[str]:
    """Rule ids allowed at ``lineno`` (1-based): from a trailing comment on
    the line itself or a standalone comment on the line above."""
    out: Set[str] = set()
    for ln in (lineno, lineno - 1):
        if 0 < ln <= len(lines):
            m = SUPPRESS_RE.search(lines[ln - 1])
            if m:
                out.update(p.strip() for p in m.group(1).split(","))
    return out


@dataclasses.dataclass
class FileReport:
    path: str
    findings: List[Finding]
    suppressed: int = 0
    error: Optional[str] = None


def index_file(path: str) -> Optional[ModuleIndex]:
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError):
        return None
    _SOURCE_CACHE[path] = source.splitlines()
    return ModuleIndex(path, tree, source)


def analyze_indexed(index: ModuleIndex, project: ProjectContext,
                    rules: Optional[Dict[str, Rule]] = None) -> FileReport:
    rules = rules if rules is not None else all_rules()
    lines = index.source.splitlines()
    findings: List[Finding] = []
    nsupp = 0
    for rule in rules.values():
        seen: Set[Tuple[str, int]] = set()
        for f in rule.check(index, project):
            key = (f.rule, f.line)
            if key in seen:        # rules may re-walk loop bodies
                continue
            seen.add(key)
            allowed = suppressed_rules(lines, f.line)
            if f.rule in allowed or "*" in allowed:
                nsupp += 1
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return FileReport(index.path, findings, nsupp)


DEFAULT_EXCLUDES = ("analysis_fixtures",)


def iter_python_files(paths: Sequence[str],
                      excludes: Sequence[str] = DEFAULT_EXCLUDES,
                      ) -> List[str]:
    """Expand dirs to .py files; explicit file paths bypass excludes (so
    tests can point the engine at the known-bad fixtures directly)."""
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in excludes
                                 and not d.startswith(".")
                                 and d != "__pycache__")
                if any(e in root.split(os.sep) for e in excludes):
                    continue
                for fn in sorted(files):
                    if fn.endswith(".py"):
                        out.append(os.path.join(root, fn))
        elif p.endswith(".py"):
            out.append(p)
    return out


def run_paths(paths: Sequence[str],
              rules: Optional[Dict[str, Rule]] = None,
              excludes: Sequence[str] = DEFAULT_EXCLUDES,
              ) -> List[FileReport]:
    """Analyze every .py under ``paths``.  Two passes: the first collects
    project-wide context (mesh axis declarations), the second runs rules."""
    files = iter_python_files(paths, excludes)
    indexes = []
    reports: List[FileReport] = []
    for path in files:
        idx = index_file(path)
        if idx is None:
            reports.append(FileReport(path, [], error="parse error"))
        else:
            indexes.append(idx)
    project = ProjectContext()
    for idx in indexes:
        project.collect(idx)
    for idx in indexes:
        reports.append(analyze_indexed(idx, project, rules))
    return reports
