"""Pass 2 (project level): cross-artifact contract rules.

These rules run once per project over the :class:`ProjectRegistry`
(pass 1, ``registry.py``) instead of once per file, and their findings
may anchor in ``.md`` files — the docs tables are artifacts under the
same zero-findings discipline as the code.

JL102 — metric contracts: every registry metric needs HELP text and a
consumer (summarize/diagnose row, docs mention, or test reference);
every sync scalar needs a consumer; a ``scalars.get`` read needs an
emitter; every docs metric-naming bullet needs an emission.

JL103 — fault-point registry: the docs/stages.md stage/point contract
table and drain-order fence must match the code-side registries (both
directions).

JL104 — config-key contracts across ALL blocks: a ``*_DEFAULT``
without its key constant, a key constant nothing reads (dead schema
key), a default nothing routes.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional

from .core import Finding, suppressed_in_lines
from .registry import ProjectRegistry

PROJECT_RULE_REGISTRY: Dict[str, type] = {}


def project_register(cls):
    PROJECT_RULE_REGISTRY[cls.id] = cls
    return cls


class ProjectRule:
    id = "JL100"
    summary = "base project rule"

    def finding(self, reg: ProjectRegistry, path: str, line: int,
                message: str) -> Finding:
        return Finding(path=path, line=line, col=0, rule=self.id,
                       message=message,
                       line_text=reg.line_text(path, line))

    def check(self, reg: ProjectRegistry) -> Iterable[Finding]:
        raise NotImplementedError


@project_register
class MetricContracts(ProjectRule):
    id = "JL102"
    summary = ("metric contract: emissions need HELP text and a "
               "consumer; docs bullets need a real metric behind "
               "them")

    def _unconsumed(self, reg, name: str, sites) -> bool:
        emitting = {p for p, _l in sites}
        return not any(occ not in emitting
                       for occ in reg.name_occurrences(name))

    def check(self, reg):
        for name, rec in sorted(reg.metrics.items()):
            path, line = rec["sites"][0]
            if not rec["has_help"]:
                yield self.finding(
                    reg, path, line,
                    f"metric '{name}' is emitted without HELP text "
                    "(pass it at the registry call site)")
            if self._unconsumed(reg, name, rec["sites"]):
                yield self.finding(
                    reg, path, line,
                    f"metric '{name}' is emitted here but consumed "
                    "nowhere — no summarize/diagnose row, docs "
                    "mention, or test reference in the tree")
        for name, sites in sorted(reg.scalars.items()):
            if self._unconsumed(reg, name, sites):
                path, line = sites[0]
                yield self.finding(
                    reg, path, line,
                    f"sync scalar '{name}' is emitted here but "
                    "consumed nowhere — no summarize row, docs "
                    "mention, or test reference in the tree")
        for name, sites in sorted(reg.scalar_reads.items()):
            if name not in reg.scalars:
                path, line = sites[0]
                yield self.finding(
                    reg, path, line,
                    f"sync scalar '{name}' is read here but no "
                    "engine ever emits it")
        known = set(reg.metrics) | set(reg.scalars)
        for name, path, line in reg.docs_metrics:
            if name not in known:
                yield self.finding(
                    reg, path, line,
                    f"documented metric '{name}' does not exist — no "
                    "registry metric or sync scalar emission has "
                    "this name")


@project_register
class FaultPointContracts(ProjectRule):
    id = "JL103"
    summary = ("fault-point registry: docs/stages.md table and "
               "drain-order fence must match the Stage/StageGraph "
               "code registries, both directions")

    def check(self, reg):
        code_pairs = {(s, p) for s, p, _f, _l in reg.fault_points
                      if s is not None}
        code_points = {p for _s, p, _f, _l in reg.fault_points}
        doc_pairs = {(s, p) for s, p, _f, _l in reg.docs_stage_rows}
        doc_points = {p for _s, p, _f, _l in reg.docs_stage_rows}

        if reg.docs_stage_rows:
            for stage, point, path, line in reg.docs_stage_rows:
                if (stage, point) not in code_pairs \
                        and point not in code_points:
                    yield self.finding(
                        reg, path, line,
                        f"documented fault point `{stage}`:`{point}` "
                        "does not exist in code — stale row vs the "
                        "stage runtime")
            for stage, point, path, line in reg.fault_points:
                if stage is not None and (stage, point) not in doc_pairs:
                    yield self.finding(
                        reg, path, line,
                        f"fault point ('{stage}', '{point}') is live "
                        "here but missing from the docs/stages.md "
                        "contract table")
                elif stage is None and point not in doc_points:
                    yield self.finding(
                        reg, path, line,
                        f"fault point '{point}' is live here but no "
                        "docs/stages.md row documents it")

        drain_names = {n for entries in reg.drain_orders.values()
                       for n, _l in entries}
        tokens = [t for t, _f, _l in reg.docs_drain]
        all_known = True
        for tok, path, line in reg.docs_drain:
            if tok not in drain_names:
                all_known = False
                yield self.finding(
                    reg, path, line,
                    f"drain-order fence token '{tok}' is not a "
                    "StageGraph.register entry (registered: "
                    f"{', '.join(sorted(drain_names)) or 'none'})")
        if tokens and all_known:
            for file, entries in sorted(reg.drain_orders.items()):
                names = [n for n, _l in entries]
                if set(tokens) <= set(names):
                    got = [n for n in names if n in set(tokens)]
                    if got != tokens:
                        path, line = (reg.docs_drain[0][1],
                                      reg.docs_drain[0][2])
                        yield self.finding(
                            reg, path, line,
                            "drain-order fence order "
                            f"{' -> '.join(tokens)} does not match "
                            f"the registration order in {file} "
                            f"({' -> '.join(got)})")
                    break
            else:
                path, line = reg.docs_drain[0][1], reg.docs_drain[0][2]
                yield self.finding(
                    reg, path, line,
                    "drain-order fence names no single "
                    "StageGraph registration sequence containing "
                    "all of: " + ", ".join(tokens))


@project_register
class ConfigKeyContracts(ProjectRule):
    id = "JL104"
    summary = ("config-key contract (all blocks): *_DEFAULT without a "
               "key constant, dead schema keys, defaults nothing "
               "routes")

    def _referenced_elsewhere(self, reg, name: str, own_file: str) -> bool:
        return any(name in refs for rp, refs in reg.upper_refs.items()
                   if rp != own_file)

    def check(self, reg):
        for dname, (path, line) in sorted(reg.config_defaults.items()):
            base = dname[: -len("_DEFAULT")]
            defined_somehow = base in reg.config_keys or \
                base in reg.upper_refs.get(path, set())
            if not defined_somehow:
                yield self.finding(
                    reg, path, line,
                    f"'{dname}' has no matching key constant "
                    f"'{base}' — a default the config schema can "
                    "never route")
                continue
            if not self._referenced_elsewhere(reg, dname, path):
                yield self.finding(
                    reg, path, line,
                    f"'{dname}' is never referenced outside "
                    f"{path} — its key is read without this default")
        for name, (value, path, line) in sorted(reg.config_keys.items()):
            if not self._referenced_elsewhere(reg, name, path):
                yield self.finding(
                    reg, path, line,
                    f"config key constant '{name}' (\"{value}\") is "
                    f"never referenced outside {path} — dead schema "
                    "key or missing validation wiring")


def run_project_rules(reg: ProjectRegistry,
                      rules: Optional[List[str]] = None) -> List[Finding]:
    out: List[Finding] = []
    for rule_id, cls in sorted(PROJECT_RULE_REGISTRY.items()):
        if rules is not None and rule_id not in rules:
            continue
        for f in cls().check(reg):
            src = reg.sources.get(f.path)
            if src is not None and f.path.endswith(".py") and \
                    suppressed_in_lines(src.splitlines(), f.line, f.rule):
                continue
            out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out
