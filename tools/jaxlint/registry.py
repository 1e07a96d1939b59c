"""Pass 1 of jaxlint v2: the project-wide cross-artifact registry.

One walk over the tree builds every registry the contract rules
(JL102/JL103/JL104, ``contracts.py``) and the interprocedural per-file
rules (JL008–JL010, ``rules.py``) reconcile:

- ``Stage(...)`` constructions and their literal names, plus the
  ``ENGINE_STAGES`` tuple and every ``StageGraph.register`` drain entry
  (``runtime/engine_stages.py``).
- Fault-point strings.  Besides direct ``fault_point(stage, point)``
  calls this resolves ONE level of wrapper indirection with a small
  fixpoint: a function whose body forwards a parameter into a known
  fault-point injector becomes an injector itself, so
  ``_write_bytes(..., point="manifest")`` and a ``point="leaf"``
  parameter default both register (checkpointing.py's style), as do
  ``stage.call("put", ...)`` / ``stage.check("job")`` sites resolved
  through in-module ``x = Stage("name")`` assignments.
- MetricsRegistry emissions (``.counter/.gauge/.histogram`` and the
  ``_count(name, help)`` module-function style) with HELP presence,
  plus the second metric plane: sync-scalar stores
  (``scalars["k"] = v`` and dict literals assigned to ``*scalars``
  names) and their ``scalars.get("k")`` readers.
- ``DS_*`` env-var reads.
- Config keys: every ``NAME = "literal"`` / ``NAME_DEFAULT`` pair in
  ``constants.py`` files and which uppercase constants each OTHER file
  references.
- The docs tables: docs/stages.md's stage/point contract table and
  drain-order fence, docs/observability.md's metric-naming bullets.

Purely syntactic, stdlib only — nothing is imported or executed.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import _SKIP_DIRS

#: fixture mini-projects live under this name; they must never leak
#: into the real tree's registry
_REGISTRY_SKIP = _SKIP_DIRS | {"jaxlint_fixtures"}

#: emissions (metrics, scalars, fault points, stages) are collected
#: from package code only — tests, tools and the benchmark CONSUME
#: metric names, they do not define the contract
_PACKAGE_TOPDIRS = {"deepspeed_tpu"}

_FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)

_METRIC_SUFFIXES = ("_total", "_seconds", "_bytes", "_in_use", "_limit")

Site = Tuple[str, int]  # (relpath, line)


def find_project_root(paths) -> Optional[str]:
    """The nearest enclosing directory holding both ``docs/`` and
    ``tools/`` — the cross-artifact surfaces the contracts reconcile.
    Checked innermost-first so fixture mini-projects that carry their
    own docs/tools are their own root."""
    for p in paths:
        d = os.path.abspath(p if os.path.isdir(p)
                            else os.path.dirname(p) or ".")
        cur = d
        while True:
            if os.path.isdir(os.path.join(cur, "docs")) and \
                    os.path.isdir(os.path.join(cur, "tools")):
                return cur
            parent = os.path.dirname(cur)
            if parent == cur:
                return None
            cur = parent
    return None


def _dotted(node) -> Optional[str]:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _const_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _is_package_path(relpath: str) -> bool:
    top = relpath.replace(os.sep, "/").split("/", 1)[0]
    return top in _PACKAGE_TOPDIRS


def _gitignored_dirs(root: str) -> Set[str]:
    """The plain directory entries (``name/``, no glob) of the root's
    ``.gitignore``, as root-relative paths: run outputs and unpacked
    copies of the tree are not the project."""
    try:
        with open(os.path.join(root, ".gitignore"), encoding="utf-8") as f:
            lines = [ln.strip() for ln in f]
    except OSError:
        return set()
    return {ln.strip("/") for ln in lines
            if ln.endswith("/") and not ln.startswith(("#", "!"))
            and not any(c in ln for c in "*?[")}


# ---------------------------------------------------------------------------
# fault-point wrapper fixpoint
# ---------------------------------------------------------------------------

#: a slot is ("const", value) or ("param", index); stage may also be
#: ("unknown",) when the wrapper cannot name its stage
_Slot = tuple


@dataclasses.dataclass
class _Injector:
    params: List[str]
    defaults: Dict[str, str]
    stage: _Slot
    point: _Slot


def _fn_params(fn) -> Tuple[List[str], Dict[str, str]]:
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args]
    defaults: Dict[str, str] = {}
    pos_defaults = args.defaults
    if pos_defaults:
        for name, d in zip(names[-len(pos_defaults):], pos_defaults):
            v = _const_str(d)
            if v is not None:
                defaults[name] = v
    for kwarg, d in zip(args.kwonlyargs, args.kw_defaults):
        names.append(kwarg.arg)
        v = _const_str(d) if d is not None else None
        if v is not None:
            defaults[kwarg.arg] = v
    return names, defaults


class _FaultPlane:
    """Resolves (stage, point) pairs through one-or-more levels of
    parameter-forwarding wrappers via a small fixpoint."""

    def __init__(self):
        # (module basename, function name) -> _Injector
        self.injectors: Dict[Tuple[str, str], _Injector] = {}
        self.sites: List[Tuple[Optional[str], str, str, int]] = []
        self._seen_sites: Set[Tuple] = set()

    def seed(self, modbase: str, fn):
        if fn.name != "fault_point":
            return
        params, defaults = _fn_params(fn)
        if len(params) >= 2 and params[0] == "stage" and params[1] == "point":
            self.injectors[(modbase, fn.name)] = _Injector(
                params, defaults, ("param", 0), ("param", 1))

    def _arg_for(self, call: ast.Call, inj: _Injector, idx: int):
        """The expression bound to the injector's idx-th parameter at
        this call, or its string default, or None."""
        if idx < len(call.args):
            return call.args[idx]
        name = inj.params[idx] if idx < len(inj.params) else None
        for kw in call.keywords:
            if kw.arg is not None and kw.arg == name:
                return kw.value
        if name is not None and name in inj.defaults:
            return inj.defaults[name]
        return None

    def _slot_value(self, slot: _Slot, call: ast.Call, inj: _Injector,
                    g_params: List[str]):
        """-> ("const", s) | ("param", caller index) | None."""
        if slot[0] == "const":
            return slot
        if slot[0] != "param":
            return ("unknown",)
        bound = self._arg_for(call, inj, slot[1])
        if bound is None:
            return None
        if isinstance(bound, str):  # a default already resolved
            return ("const", bound)
        s = _const_str(bound)
        if s is not None:
            return ("const", s)
        if isinstance(bound, ast.Name) and bound.id in g_params:
            return ("param", g_params.index(bound.id))
        return None

    def visit(self, modbase: str, relpath: str, g_name: str,
              g_params: List[str], g_defaults: Dict[str, str],
              body_nodes, alias_map: Dict[str, Tuple[str, str]]) -> bool:
        """Scan one function (or the module pseudo-function) for calls
        into known injectors; returns True when new facts appeared."""
        changed = False
        for node in body_nodes:
            if not isinstance(node, ast.Call):
                continue
            callee = node.func
            key = None
            if isinstance(callee, ast.Name):
                key = alias_map.get(callee.id)
            if key is None or key not in self.injectors:
                continue
            inj = self.injectors[key]
            stage_v = self._slot_value(inj.stage, node, inj, g_params)
            point_v = self._slot_value(inj.point, node, inj, g_params)
            if point_v is None:
                continue
            if stage_v is not None and stage_v[0] == "const" \
                    and point_v[0] == "const":
                site = (stage_v[1], point_v[1], relpath, node.lineno)
                if site not in self._seen_sites:
                    self._seen_sites.add(site)
                    self.sites.append(site)
                    changed = True
            elif stage_v == ("unknown",) and point_v[0] == "const":
                site = (None, point_v[1], relpath, node.lineno)
                if site not in self._seen_sites:
                    self._seen_sites.add(site)
                    self.sites.append(site)
                    changed = True
            elif point_v[0] == "param" and g_name is not None:
                new_stage = stage_v if stage_v is not None \
                    and stage_v[0] == "const" else ("unknown",)
                key2 = (modbase, g_name)
                if key2 not in self.injectors:
                    self.injectors[key2] = _Injector(
                        g_params, g_defaults, new_stage, point_v)
                    changed = True
        return changed


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProjectRegistry:
    root: str
    files: List[str] = dataclasses.field(default_factory=list)
    sources: Dict[str, str] = dataclasses.field(default_factory=dict)
    # stage plane
    stages: Dict[str, List[Site]] = dataclasses.field(default_factory=dict)
    engine_stages: List[str] = dataclasses.field(default_factory=list)
    drain_orders: Dict[str, List[Tuple[str, int]]] = \
        dataclasses.field(default_factory=dict)
    fault_points: List[Tuple[Optional[str], str, str, int]] = \
        dataclasses.field(default_factory=list)
    # metric planes
    metrics: Dict[str, dict] = dataclasses.field(default_factory=dict)
    scalars: Dict[str, List[Site]] = dataclasses.field(default_factory=dict)
    scalar_reads: Dict[str, List[Site]] = \
        dataclasses.field(default_factory=dict)
    env_vars: Dict[str, List[Site]] = dataclasses.field(default_factory=dict)
    # config plane
    config_keys: Dict[str, Tuple[str, str, int]] = \
        dataclasses.field(default_factory=dict)
    config_defaults: Dict[str, Site] = dataclasses.field(default_factory=dict)
    upper_refs: Dict[str, Set[str]] = dataclasses.field(default_factory=dict)
    # docs plane
    docs_stage_rows: List[Tuple[str, str, str, int]] = \
        dataclasses.field(default_factory=list)
    docs_drain: List[Tuple[str, str, int]] = \
        dataclasses.field(default_factory=list)
    docs_metrics: List[Tuple[str, str, int]] = \
        dataclasses.field(default_factory=list)

    # -- queries ---------------------------------------------------------
    def line_text(self, relpath: str, lineno: int) -> str:
        src = self.sources.get(relpath)
        if src is None:
            try:
                with open(os.path.join(self.root, relpath),
                          encoding="utf-8") as f:
                    src = f.read()
            except OSError:
                src = ""
            self.sources[relpath] = src
        lines = src.splitlines()
        if 1 <= lineno <= len(lines):
            return lines[lineno - 1]
        return ""

    def known_stage_names(self) -> Set[str]:
        """The stage NAMESPACE: ENGINE_STAGES + docs contract table +
        stage constants at resolved fault points (e.g. ``ckpt``, which
        is never a ``Stage(...)`` construction)."""
        names = set(self.engine_stages)
        names.update(s for s, _p, _f, _l in self.docs_stage_rows)
        names.update(s for s, _p, _f, _l in self.fault_points
                     if s is not None)
        return names

    def name_occurrences(self, name: str) -> List[str]:
        """Files whose text mentions ``name`` as a whole word."""
        pat = re.compile(r"(?<![A-Za-z0-9_])%s(?![A-Za-z0-9_])"
                         % re.escape(name))
        # the substring test is the cheap necessary condition: without
        # it 93 names x 250 files of regex are 4 of the pre-flight's 7 s
        return [rp for rp, src in sorted(self.sources.items())
                if name in src and pat.search(src)]

    # -- construction ----------------------------------------------------
    @classmethod
    def build(cls, root: str) -> "ProjectRegistry":
        reg = cls(root=os.path.abspath(root))
        reg._scan_py_files()
        reg._scan_docs()
        return reg

    def _iter_files(self, suffix: str) -> List[str]:
        out = []
        ignored = _gitignored_dirs(self.root)
        for dirpath, dirs, names in os.walk(self.root):
            below = os.path.relpath(dirpath, self.root).replace(os.sep, "/")
            dirs[:] = sorted(
                d for d in dirs if d not in _REGISTRY_SKIP
                and not d.startswith(".")
                and not {d, f"{below}/{d}"} & ignored)
            for n in sorted(names):
                if n.endswith(suffix):
                    out.append(os.path.relpath(os.path.join(dirpath, n),
                                               self.root))
        return out

    def _scan_py_files(self):
        trees: Dict[str, ast.AST] = {}
        for rp in self._iter_files(".py"):
            try:
                with open(os.path.join(self.root, rp),
                          encoding="utf-8") as f:
                    src = f.read()
                tree = ast.parse(src)
            except (OSError, SyntaxError):
                continue
            self.files.append(rp)
            self.sources[rp] = src
            trees[rp] = tree
            self.upper_refs[rp] = set(
                re.findall(r"\b[A-Z][A-Z0-9_]{2,}\b", src))
        for rp, tree in trees.items():
            self._scan_module(rp, tree)
        self._resolve_fault_points(trees)

    # -- per-module extraction -------------------------------------------
    def _scan_module(self, rp: str, tree):
        in_pkg = _is_package_path(rp)
        is_constants = os.path.basename(rp) == "constants.py"
        if is_constants:
            self._scan_constants(rp, tree)
        stage_vars = self._stage_assignments(tree) if in_pkg else {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and in_pkg:
                self._scan_assign(rp, node)
            if isinstance(node, ast.Subscript):
                self._scan_subscript(rp, node, in_pkg)
            if not isinstance(node, ast.Call):
                continue
            if not in_pkg:
                continue
            self._scan_env_call(rp, node)
            self._scan_metric_call(rp, node)
            self._scan_scalar_get(rp, node)
            fn = node.func
            last = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None)
            if last == "Stage" and node.args:
                name = _const_str(node.args[0])
                if name is not None:
                    self.stages.setdefault(name, []).append(
                        (rp, node.lineno))
            elif last == "register" and isinstance(fn, ast.Attribute) \
                    and node.args and any(k.arg in ("close", "drain")
                                          for k in node.keywords):
                name = _const_str(node.args[0])
                if name is not None:
                    self.drain_orders.setdefault(rp, []).append(
                        (name, node.lineno))
            elif last in ("call", "check") and isinstance(fn, ast.Attribute) \
                    and node.args:
                point = _const_str(node.args[0])
                recv = _dotted(fn.value)
                if point is not None and recv is not None:
                    if recv in stage_vars:
                        self.fault_points.append(
                            (stage_vars[recv], point, rp, node.lineno))
                    elif "stage" in recv.lower():
                        self.fault_points.append(
                            (None, point, rp, node.lineno))
        if in_pkg:
            for stmt in tree.body:
                if isinstance(stmt, ast.Assign) \
                        and len(stmt.targets) == 1 \
                        and isinstance(stmt.targets[0], ast.Name) \
                        and stmt.targets[0].id == "ENGINE_STAGES" \
                        and isinstance(stmt.value, (ast.Tuple, ast.List)):
                    for elt in stmt.value.elts:
                        if isinstance(elt, (ast.Tuple, ast.List)) \
                                and elt.elts:
                            name = _const_str(elt.elts[0])
                            if name is not None:
                                self.engine_stages.append(name)

    def _stage_assignments(self, tree) -> Dict[str, str]:
        """dotted assignment target -> stage name, for every assignment
        whose value subtree contains ``Stage("<literal>")`` (covers the
        ``x = given or Stage("n")`` ternary/boolean fallbacks)."""
        out: Dict[str, str] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            name = None
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Call):
                    last = sub.func.attr \
                        if isinstance(sub.func, ast.Attribute) else (
                            sub.func.id if isinstance(sub.func, ast.Name)
                            else None)
                    if last == "Stage" and sub.args:
                        name = _const_str(sub.args[0])
                        if name is not None:
                            break
            if name is None:
                continue
            for tgt in node.targets:
                text = _dotted(tgt)
                if text is not None:
                    out[text] = name
        return out

    def _scan_metric_call(self, rp: str, node: ast.Call):
        fn = node.func
        kind = None
        if isinstance(fn, ast.Attribute) and fn.attr in (
                "counter", "gauge", "histogram"):
            kind = fn.attr
        elif ((isinstance(fn, ast.Name) and fn.id == "_count")
              or (isinstance(fn, ast.Attribute) and fn.attr == "_count")):
            kind = "counter"
        if kind is None or not node.args:
            return
        name = _const_str(node.args[0])
        if name is None:
            return
        has_help = (len(node.args) > 1
                    and _const_str(node.args[1]) is not None) or any(
            kw.arg == "help" and _const_str(kw.value) is not None
            for kw in node.keywords)
        rec = self.metrics.setdefault(
            name, {"kind": kind, "has_help": False, "sites": []})
        rec["has_help"] = rec["has_help"] or has_help
        rec["sites"].append((rp, node.lineno))

    def _scan_assign(self, rp: str, node: ast.Assign):
        # scalars = {"name": value, ...}  (the dict-literal plane)
        if not isinstance(node.value, ast.Dict):
            return
        for tgt in node.targets:
            text = _dotted(tgt)
            if text is None or "scalar" not in text.split(".")[-1].lower():
                continue
            for k in node.value.keys:
                name = _const_str(k) if k is not None else None
                if name is not None:
                    self.scalars.setdefault(name, []).append(
                        (rp, node.lineno))

    def _scan_subscript(self, rp: str, node: ast.Subscript, in_pkg: bool):
        if not in_pkg:
            return
        recv = _dotted(node.value)
        if recv is None or "scalar" not in recv.split(".")[-1].lower():
            return
        name = _const_str(node.slice)
        if name is None:
            return
        if isinstance(node.ctx, ast.Store) and in_pkg:
            self.scalars.setdefault(name, []).append((rp, node.lineno))
        elif isinstance(node.ctx, ast.Load):
            self.scalar_reads.setdefault(name, []).append((rp, node.lineno))

    def _scan_scalar_get(self, rp: str, node: ast.Call):
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr == "get"
                and node.args):
            return
        recv = _dotted(fn.value)
        if recv is None or "scalar" not in recv.split(".")[-1].lower():
            return
        name = _const_str(node.args[0])
        if name is not None:
            self.scalar_reads.setdefault(name, []).append((rp, node.lineno))

    def _scan_env_call(self, rp: str, node: ast.Call):
        fn = node.func
        text = _dotted(fn) or ""
        name = None
        if text.endswith("getenv") and node.args:
            name = _const_str(node.args[0])
        elif isinstance(fn, ast.Attribute) and fn.attr in ("get", "pop") \
                and node.args and (_dotted(fn.value) or "").endswith(
                    "environ"):
            name = _const_str(node.args[0])
        if name is not None and name.startswith("DS_"):
            self.env_vars.setdefault(name, []).append((rp, node.lineno))

    def _scan_constants(self, rp: str, tree):
        for stmt in tree.body:
            if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                continue
            name = stmt.targets[0].id
            if not re.fullmatch(r"[A-Z][A-Z0-9_]*", name):
                continue
            if name.endswith("_DEFAULT"):
                self.config_defaults[name] = (rp, stmt.lineno)
            else:
                v = _const_str(stmt.value)
                if v is not None:
                    self.config_keys[name] = (v, rp, stmt.lineno)

    # -- fault-point fixpoint --------------------------------------------
    def _resolve_fault_points(self, trees: Dict[str, ast.AST]):
        plane = _FaultPlane()
        modules = []  # (modbase, rp, alias_map, functions)
        for rp, tree in trees.items():
            if not _is_package_path(rp):
                continue
            modbase = os.path.basename(rp)[:-3]
            alias: Dict[str, Tuple[str, str]] = {}
            for node in ast.walk(tree):
                if isinstance(node, ast.ImportFrom) and node.module \
                        is not None:
                    src = node.module.split(".")[-1]
                    for a in node.names:
                        alias[a.asname or a.name] = (src, a.name)
            funcs = []
            module_level: List[ast.AST] = []
            for stmt in tree.body:
                if isinstance(stmt, _FUNC_DEFS):
                    funcs.append(stmt)
                    plane.seed(modbase, stmt)
                    alias.setdefault(stmt.name, (modbase, stmt.name))
                elif isinstance(stmt, ast.ClassDef):
                    for sub in stmt.body:
                        if isinstance(sub, _FUNC_DEFS):
                            funcs.append(sub)
                else:
                    module_level.append(stmt)
            modules.append((modbase, rp, alias, funcs, module_level))
        for _ in range(6):
            changed = False
            for modbase, rp, alias, funcs, module_level in modules:
                for fn in funcs:
                    params, defaults = _fn_params(fn)
                    body = [n for n in ast.walk(fn)]
                    if plane.visit(modbase, rp, fn.name, params, defaults,
                                   body, alias):
                        changed = True
                flat = [n for stmt in module_level
                        for n in ast.walk(stmt)]
                if plane.visit(modbase, rp, None, [], {}, flat, alias):
                    changed = True
            if not changed:
                break
        self.fault_points.extend(plane.sites)
        self.fault_points.sort(key=lambda t: (t[2], t[3]))

    # -- non-python artifacts --------------------------------------------
    def _read_doc(self, relpath: str) -> Optional[List[str]]:
        path = os.path.join(self.root, relpath)
        if not os.path.isfile(path):
            return None
        with open(path, encoding="utf-8") as f:
            src = f.read()
        self.sources[relpath] = src
        return src.splitlines()

    def _scan_docs(self):
        stages_rp = os.path.join("docs", "stages.md")
        lines = self._read_doc(stages_rp)
        if lines is not None:
            self._scan_stage_table(stages_rp, lines)
            self._scan_drain_fence(stages_rp, lines)
        obs_rp = os.path.join("docs", "observability.md")
        lines = self._read_doc(obs_rp)
        if lines is not None:
            self._scan_metric_bullets(obs_rp, lines)
        # the rest of docs/ + README joins the consumer corpus
        for rp in self._iter_files(".md"):
            if rp not in self.sources:
                try:
                    with open(os.path.join(self.root, rp),
                              encoding="utf-8") as f:
                        self.sources[rp] = f.read()
                except OSError:
                    pass

    def _scan_stage_table(self, rp: str, lines: List[str]):
        in_table = False
        for i, line in enumerate(lines, 1):
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if not line.lstrip().startswith("|"):
                in_table = False
                continue
            if len(cells) >= 2 and cells[0] == "stage" \
                    and cells[1] == "point":
                in_table = True
                continue
            if not in_table or len(cells) < 2:
                continue
            if set(cells[0]) <= {"-", " ", ":"}:
                continue
            m = re.findall(r"`([A-Za-z0-9_]+)`", cells[0])
            if not m:
                continue
            stage = m[0]
            for point in re.findall(r"`([A-Za-z0-9_]+)`", cells[1]):
                self.docs_stage_rows.append((stage, point, rp, i))

    def _scan_drain_fence(self, rp: str, lines: List[str]):
        in_section = False
        in_fence = False
        for i, line in enumerate(lines, 1):
            if line.startswith("#") and "drain order" in line.lower():
                in_section = True
                continue
            if in_section and line.startswith("#"):
                break
            if not in_section:
                continue
            if line.strip().startswith("```"):
                if in_fence:
                    break
                in_fence = True
                continue
            if in_fence and ("→" in line or "->" in line):
                for tok in re.split(r"→|->", line):
                    tok = " ".join(tok.split())
                    if tok:
                        self.docs_drain.append((tok, rp, i))

    def _scan_metric_bullets(self, rp: str, lines: List[str]):
        in_section = False
        for i, line in enumerate(lines, 1):
            if line.startswith("## "):
                in_section = "metric naming" in line.lower()
                continue
            if not in_section:
                continue
            for tok in re.findall(r"`([a-z][a-z0-9_]*)(?:\{[^`]*)?`", line):
                if tok.endswith(_METRIC_SUFFIXES):
                    self.docs_metrics.append((tok, rp, i))

    # -- dump ------------------------------------------------------------
    def dump(self) -> dict:
        """A JSON-stable snapshot (``--registry-dump``)."""
        return {
            "root": self.root,
            "stages": {k: sorted(v) for k, v in sorted(self.stages.items())},
            "engine_stages": list(self.engine_stages),
            "drain_orders": {k: v for k, v in
                             sorted(self.drain_orders.items())},
            "fault_points": [[s, p, f, l] for s, p, f, l in
                             sorted(self.fault_points,
                                    key=lambda t: (t[2], t[3]))],
            "metrics": {k: {"kind": v["kind"], "has_help": v["has_help"],
                            "sites": sorted(v["sites"])}
                        for k, v in sorted(self.metrics.items())},
            "scalars": {k: sorted(v) for k, v in
                        sorted(self.scalars.items())},
            "scalar_reads": {k: sorted(v) for k, v in
                             sorted(self.scalar_reads.items())},
            "env_vars": {k: sorted(v) for k, v in
                         sorted(self.env_vars.items())},
            "config_keys": {k: list(v) for k, v in
                            sorted(self.config_keys.items())},
            "config_defaults": {k: list(v) for k, v in
                                sorted(self.config_defaults.items())},
            "docs_stage_rows": [list(r) for r in self.docs_stage_rows],
            "docs_drain": [list(r) for r in self.docs_drain],
            "docs_metrics": [list(r) for r in self.docs_metrics],
        }
