"""graftlint core: file walking, suppressions, baseline ratchet, formats.

Everything here is stdlib-only (``ast`` + ``json``); rules live in
``rules.py`` and come in two shapes:

* per-file rules:    ``check(pf: ParsedFile) -> Iterable[Finding]``
* project rules:     subclasses of ``ProjectRule`` — they run once after
  every file parses, over the whole-program ``ProjectIndex`` built by
  ``project.py`` (GL005/GL006 need the config/fault registries vs every
  use site; GL017–GL020 need the cross-class lock graph and the
  probe/trial tables).  They emit findings anchored to real file:line so
  baselines and suppressions work unchanged.

Passing ``cache_path`` to ``run`` enables the content-hash index cache:
unchanged files skip re-parsing AND re-running per-file rules (their
facts and findings replay from ``.graftlint_index.json``).

Suppression is per line: ``# graftlint: disable=GL001`` (or a comma list,
or bare ``disable`` for all rules) on the finding's line.

Baseline ratchet: ``baseline.json`` holds fingerprints of grandfathered
findings.  A finding whose fingerprint — ``(rule, path, stripped source
line)``, deliberately line-number-free so pure code motion doesn't churn
it — is in the baseline is reported as a warning; anything else fails the
run.  Baseline entries matching nothing are "stale" (burned down): the
run stays green and prints them so ``--write-baseline`` can shrink the
file, never grow it back.
"""

from __future__ import annotations

import ast
import json
import os
import re
import tokenize
from dataclasses import dataclass, field
from io import StringIO
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable(?:=(?P<rules>[A-Z0-9, ]+))?")

_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", ".hypothesis",
              "build", "node_modules", ".venv"}


@dataclass
class Finding:
    rule: str
    path: str           # project-root-relative, posix separators
    line: int
    col: int
    message: str
    snippet: str        # stripped source of the finding line
    status: str = "new"  # new | baselined | suppressed

    def fingerprint(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.snippet)

    def as_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message,
                "snippet": self.snippet, "status": self.status}


@dataclass
class ParsedFile:
    path: str                      # absolute
    relpath: str                   # project-root-relative, posix
    source: str
    tree: ast.AST
    lines: List[str]
    # line -> None (all rules suppressed) or the set of suppressed rules
    suppressions: Dict[int, Optional[Set[str]]] = field(default_factory=dict)

    @property
    def is_test_file(self) -> bool:
        parts = self.relpath.split("/")
        base = parts[-1]
        return ("tests" in parts[:-1] or base.startswith("test_")
                or base.startswith("conftest"))

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def finding(self, rule: str, node_or_line, message: str) -> Finding:
        if isinstance(node_or_line, int):
            line, col = node_or_line, 0
        else:
            line = getattr(node_or_line, "lineno", 1)
            col = getattr(node_or_line, "col_offset", 0)
        return Finding(rule=rule, path=self.relpath, line=line, col=col,
                       message=message, snippet=self.line_text(line))

    def suppressed(self, f: Finding) -> bool:
        if f.line not in self.suppressions:
            return False
        rules = self.suppressions[f.line]
        return rules is None or f.rule in rules


def _scan_suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
    """Comment lines carrying ``# graftlint: disable[=GLnnn,...]``."""
    out: Dict[int, Optional[Set[str]]] = {}
    try:
        tokens = tokenize.generate_tokens(StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            rules = m.group("rules")
            if rules is None:
                out[tok.start[0]] = None
            else:
                got = {r.strip() for r in rules.split(",") if r.strip()}
                prev = out.get(tok.start[0], set())
                out[tok.start[0]] = None if prev is None else (prev | got)
    except tokenize.TokenError:
        pass
    return out


def parse_file(path: str, root: str) -> Optional[ParsedFile]:
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError, ValueError):
        return None
    rel = os.path.relpath(os.path.abspath(path), root).replace(os.sep, "/")
    return ParsedFile(path=os.path.abspath(path), relpath=rel, source=source,
                      tree=tree, lines=source.splitlines(),
                      suppressions=_scan_suppressions(source))


class ProjectRule:
    """Protocol for whole-program rules.

    ``check_index`` runs once, after all files parse, over the
    ``project.ProjectIndex``; ``linted`` is the ordered list of relpaths
    actually being linted this run (the index itself covers the whole
    tree — rules use ``linted`` to keep findings on the files the user
    asked about).  Findings must anchor to real file:line positions so
    the baseline ratchet and per-line suppressions work unchanged.
    """

    id: str = ""
    per_file: bool = False
    uses_index: bool = True

    def check(self, pf: "ParsedFile") -> Iterable[Finding]:
        return ()

    def check_index(self, index, linted: List[str],
                    project: "Project") -> Iterable[Finding]:
        return ()


def _walk_py(target: str) -> Iterable[str]:
    if os.path.isfile(target):
        if target.endswith(".py"):
            yield target
        return
    for dirpath, dirnames, filenames in os.walk(target):
        dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
        for name in sorted(filenames):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


@dataclass
class Project:
    """Cross-file context handed to project rules."""
    root: str
    files: List[ParsedFile]                 # the files being linted
    _universe: Optional[List[ParsedFile]] = None

    def readme_text(self) -> str:
        try:
            with open(os.path.join(self.root, "README.md"),
                      encoding="utf-8") as f:
                return f.read()
        except OSError:
            return ""

    def universe(self) -> List[ParsedFile]:
        """Every .py under the project root (reads/uses may legitimately
        live outside the linted paths — __graft_entry__.py,
        chip_smoke.py, tools/ scripts)."""
        if self._universe is None:
            seen = {pf.path for pf in self.files}
            extra = []
            for path in _walk_py(self.root):
                ap = os.path.abspath(path)
                if ap in seen:
                    continue
                pf = parse_file(ap, self.root)
                if pf is not None:
                    extra.append(pf)
            self._universe = list(self.files) + extra
        return self._universe


@dataclass
class LintResult:
    findings: List[Finding]
    stale_baseline: List[dict]
    parse_errors: List[str]

    @property
    def new(self) -> List[Finding]:
        return [f for f in self.findings if f.status == "new"]

    @property
    def exit_code(self) -> int:
        return 1 if self.new else 0

    def counts(self) -> Dict[str, int]:
        c = {"new": 0, "baselined": 0, "suppressed": 0}
        for f in self.findings:
            c[f.status] += 1
        return c

    def to_json(self) -> str:
        return json.dumps(
            {"findings": [f.as_dict() for f in self.findings],
             "counts": self.counts(),
             "stale_baseline": self.stale_baseline,
             "parse_errors": self.parse_errors,
             "exit_code": self.exit_code},
            indent=2, sort_keys=False) + "\n"

    def to_sarif(self) -> str:
        """Minimal SARIF 2.1.0 — enough for code-scanning uploads and
        editor ingestion.  Suppressed findings are omitted; baselined
        ones downgrade to ``note``."""
        results = []
        for f in sorted(self.findings,
                        key=lambda f: (f.path, f.line, f.rule)):
            if f.status == "suppressed":
                continue
            results.append({
                "ruleId": f.rule,
                "level": "error" if f.status == "new" else "note",
                "message": {"text": f.message},
                "locations": [{
                    "physicalLocation": {
                        "artifactLocation": {"uri": f.path},
                        "region": {"startLine": f.line,
                                   "startColumn": f.col + 1},
                    }}],
            })
        doc = {
            "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                        "sarif-spec/master/Schemata/sarif-schema-2.1.0"
                        ".json"),
            "version": "2.1.0",
            "runs": [{
                "tool": {"driver": {
                    "name": "graftlint",
                    "informationUri":
                        "tools/graftlint/README.md",
                    "rules": [{"id": rid} for rid in sorted(
                        {f.rule for f in self.findings})],
                }},
                "results": results,
            }],
        }
        return json.dumps(doc, indent=2) + "\n"

    def to_text(self) -> str:
        out = []
        for f in sorted(self.findings,
                        key=lambda f: (f.path, f.line, f.rule)):
            tag = "" if f.status == "new" else f" [{f.status}]"
            out.append(f"{f.path}:{f.line}:{f.col}: "
                       f"{f.rule} {f.message}{tag}")
        c = self.counts()
        out.append(f"graftlint: {c['new']} new, {c['baselined']} baselined, "
                   f"{c['suppressed']} suppressed"
                   + (f", {len(self.stale_baseline)} stale baseline "
                      f"entr{'y' if len(self.stale_baseline) == 1 else 'ies'}"
                      " (burned down — rewrite with --write-baseline)"
                      if self.stale_baseline else ""))
        for err in self.parse_errors:
            out.append(f"graftlint: PARSE ERROR {err}")
        return "\n".join(out) + "\n"


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def load_baseline(path: Optional[str]) -> List[dict]:
    if not path or not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    return list(doc.get("findings", []))


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    entries = sorted(
        {f.fingerprint() for f in findings if f.status != "suppressed"})
    doc = {"comment": "graftlint ratchet: grandfathered findings. "
                      "Entries only ever leave this file.",
           "findings": [{"rule": r, "path": p, "snippet": s}
                        for (r, p, s) in entries]}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def _facts_suppressed(facts: Optional[dict], line: int, rule: str) -> bool:
    """Suppression check for findings on files we never re-parsed (cache
    hits and universe files) — the suppression table travels with the
    facts record."""
    if not facts:
        return False
    entry = facts.get("suppressions", {}).get(str(line), "absent")
    if entry == "absent":
        return False
    return entry is None or rule in entry


def run(paths: Sequence[str], root: Optional[str] = None,
        baseline: Optional[Sequence[dict]] = None,
        rules: Optional[Sequence[str]] = None,
        cache_path: Optional[str] = None) -> LintResult:
    """Lint ``paths`` (files or directories) and classify findings.

    ``root`` anchors relative paths, README lookup and the read-universe;
    it defaults to the repo root (two levels above this file).  ``rules``
    optionally restricts to a subset of rule ids (for tests).
    ``cache_path`` enables the content-hash index cache: unchanged files
    replay their facts and per-file findings from the cache instead of
    being re-parsed.
    """
    from . import project as project_mod
    from . import rules as rules_mod

    if root is None:
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    root = os.path.abspath(root)

    active = rules_mod.all_rules(only=rules)
    per_file_rules = [r for r in active if r.per_file]
    index_rules = [r for r in active
                   if not r.per_file and getattr(r, "uses_index", False)]
    legacy_rules = [r for r in active
                    if not r.per_file and not getattr(r, "uses_index",
                                                      False)]

    cache = None
    if cache_path:
        sig = "|".join(r.id for r in active)
        cache = project_mod.IndexCache(cache_path, sig)
    # legacy (non-index) project rules inspect real ParsedFiles, so cache
    # hits cannot stand in for parses while one is active
    reuse = cache is not None and not legacy_rules

    files: List[ParsedFile] = []
    parse_errors: List[str] = []
    seen: Set[str] = set()
    linted_rels: List[str] = []
    facts_by_rel: Dict[str, dict] = {}
    findings: List[Finding] = []

    for target in paths:
        for path in _walk_py(target):
            ap = os.path.abspath(path)
            if ap in seen:
                continue
            seen.add(ap)
            rel = os.path.relpath(ap, root).replace(os.sep, "/")
            if cache is not None:
                try:
                    with open(ap, encoding="utf-8") as f:
                        digest = project_mod.content_hash(f.read())
                except OSError:
                    parse_errors.append(rel)
                    continue
                entry = cache.lookup(rel, digest) if reuse else None
                if entry is not None and entry.get("findings") is not None:
                    linted_rels.append(rel)
                    facts_by_rel[rel] = entry["facts"]
                    for fd in entry["findings"]:
                        findings.append(Finding(
                            rule=fd["rule"], path=fd["path"],
                            line=fd["line"], col=fd["col"],
                            message=fd["message"], snippet=fd["snippet"]))
                    continue
            pf = parse_file(ap, root)
            if pf is None:
                parse_errors.append(rel)
                continue
            files.append(pf)
            linted_rels.append(pf.relpath)

    project = Project(root=root, files=files)

    for pf in files:
        pf_findings: List[Finding] = []
        for rule in per_file_rules:
            pf_findings.extend(rule.check(pf))
        findings.extend(pf_findings)
        if cache is not None or index_rules:
            facts = project_mod.extract_facts(pf)
            facts_by_rel[pf.relpath] = facts
            if cache is not None:
                cache.store(pf.relpath,
                            project_mod.content_hash(pf.source), facts,
                            [f.as_dict() for f in pf_findings])

    if index_rules:
        # the index spans the whole tree, not just the linted paths —
        # registries and their use sites may live on either side
        for path in _walk_py(root):
            ap = os.path.abspath(path)
            rel = os.path.relpath(ap, root).replace(os.sep, "/")
            if rel in facts_by_rel:
                continue
            try:
                with open(ap, encoding="utf-8") as f:
                    digest = project_mod.content_hash(f.read())
            except OSError:
                continue
            entry = cache.lookup(rel, digest) if cache is not None else None
            if entry is not None:
                facts_by_rel[rel] = entry["facts"]
                continue
            pf = parse_file(ap, root)
            if pf is None:
                continue
            facts = project_mod.extract_facts(pf)
            facts_by_rel[rel] = facts
            if cache is not None:
                cache.store(rel, digest, facts, None)
        index = project_mod.ProjectIndex(root=root, modules=facts_by_rel,
                                         readme=project.readme_text())
        for rule in index_rules:
            findings.extend(rule.check_index(index, linted_rels, project))

    for rule in legacy_rules:
        findings.extend(rule.check_project(files, project))

    if cache is not None:
        cache.save()

    by_path = {pf.relpath: pf for pf in files}
    base_index: Dict[Tuple[str, str, str], dict] = {
        (e["rule"], e["path"], e["snippet"]): e for e in (baseline or [])}
    matched: Set[Tuple[str, str, str]] = set()
    for f in findings:
        pf = by_path.get(f.path)
        if pf is not None:
            sup = pf.suppressed(f)
        else:
            sup = _facts_suppressed(facts_by_rel.get(f.path), f.line,
                                    f.rule)
        if sup:
            f.status = "suppressed"
        elif f.fingerprint() in base_index:
            f.status = "baselined"
            matched.add(f.fingerprint())
    stale = [e for k, e in base_index.items() if k not in matched]
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(findings=findings, stale_baseline=stale,
                      parse_errors=parse_errors)
