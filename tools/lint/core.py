"""graftlint core: file model, suppressions, baseline, reports.

The analyzer is two-phase. Phase 1 parses every scanned file into a
`FileInfo` (AST + a line->comment map from tokenize) — files are
independent, so this runs on a thread pool. Phase 2 runs each rule over
the WHOLE file set: the repo's invariants are cross-file by nature
(LGT001 joins config.py against three other modules), so rules see
everything and pick what they need.

Suppression model, narrowest first:

* inline — ``# graftlint: disable=LGT00x reason`` on the finding's line
  (or on a standalone comment line directly above it). The reason text
  is mandatory by policy (docs/Linting.md), not by parser.
* baseline — ``tools/lint/baseline.json`` maps finding fingerprints to
  grandfathered counts. Fingerprints hash (rule, path, message) but NOT
  the line number, so unrelated edits above a finding don't churn the
  baseline; duplicate findings match count-wise. The repo policy keeps
  the baseline EMPTY for LGT001/LGT002 (those findings are always fixed,
  never grandfathered).

Exit contract: nonzero on any new finding or any unparseable file.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import io
import json
import os
import re
import tokenize
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

JSON_SCHEMA_VERSION = 1

# what `python -m tools.lint` scans by default, relative to the repo
# root. tests/ is deliberately absent: fixtures there VIOLATE the
# invariants on purpose.
DEFAULT_SCAN: Tuple[str, ...] = (
    "lightgbm_tpu", "tools", "__graft_entry__.py")
_SKIP_DIRS = {"__pycache__", ".git", "build", "dist"}

_SUPPRESS_RE = re.compile(
    r"graftlint:\s*disable=((?:LGT\d{3})(?:\s*,\s*LGT\d{3})*)")
_PARSE_RULE = "LGT000"   # reserved: file failed to parse


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str        # repo-relative, forward slashes
    line: int
    message: str

    @property
    def fingerprint(self) -> str:
        blob = f"{self.rule}|{self.path}|{self.message}"
        return hashlib.sha1(blob.encode()).hexdigest()[:16]

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def to_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message, "fingerprint": self.fingerprint}


class FileInfo:
    """One parsed source file: AST plus the comment/suppression maps the
    rules share (tokenize runs once here, not once per rule)."""

    def __init__(self, path: str, relpath: str, source: str) -> None:
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.parse_error: Optional[str] = None
        try:
            self.tree: Optional[ast.AST] = ast.parse(source, filename=path)
        except SyntaxError as exc:
            self.tree = None
            self.parse_error = f"{exc.msg} (line {exc.lineno})"
        self.comments: Dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(source).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
        except (tokenize.TokenError, IndentationError):
            pass
        self.suppressions = self._build_suppressions()

    def _build_suppressions(self) -> Dict[int, Set[str]]:
        """line -> rule ids suppressed there. A directive on a code line
        covers that line; on a standalone comment line it covers the
        next line (stacked standalone comments chain downward)."""
        out: Dict[int, Set[str]] = {}
        for line, comment in self.comments.items():
            m = _SUPPRESS_RE.search(comment)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",")}
            target = line
            code = (self.lines[line - 1]
                    if line - 1 < len(self.lines) else "")
            if code.lstrip().startswith("#"):
                target = line + 1
            out.setdefault(target, set()).update(rules)
        # chain: a standalone directive above another standalone comment
        # walks down to the first code line
        changed = True
        while changed:
            changed = False
            for line in list(out):
                code = (self.lines[line - 1]
                        if line - 1 < len(self.lines) else "")
                if code.lstrip().startswith("#"):
                    out.setdefault(line + 1, set()).update(out.pop(line))
                    changed = True
        return out

    def suppressed(self, line: int, rule: str) -> bool:
        return rule in self.suppressions.get(line, ())


def _is_py(name: str) -> bool:
    return name.endswith(".py")


def collect_paths(root: str,
                  scan: Sequence[str] = DEFAULT_SCAN) -> List[str]:
    """Absolute paths of every .py file under the scan roots."""
    out: List[str] = []
    for rel in scan:
        top = os.path.join(root, rel)
        if os.path.isfile(top) and _is_py(top):
            out.append(top)
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _SKIP_DIRS)
            for name in sorted(filenames):
                if _is_py(name):
                    out.append(os.path.join(dirpath, name))
    return out


def load_files(root: str, paths: Iterable[str],
               jobs: int = 0) -> List[FileInfo]:
    """Phase 1: parse all files on a thread pool (parse + tokenize
    release little, but I/O overlaps and the pool keeps the driver
    simple; --jobs 1 degrades to serial for debugging)."""
    paths = list(paths)

    def _load(path: str) -> FileInfo:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        return FileInfo(path, os.path.relpath(path, root), src)

    if jobs == 1 or len(paths) < 2:
        return [_load(p) for p in paths]
    workers = jobs if jobs > 0 else min(8, (os.cpu_count() or 2))
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(_load, paths))


def find_file(files: Sequence[FileInfo],
              suffix: str) -> Optional[FileInfo]:
    """The scanned file whose relpath ends with `suffix` (rules locate
    their cross-file anchors this way, so fixture trees in tests only
    need to reproduce the tail of the layout)."""
    for f in files:
        if f.relpath == suffix or f.relpath.endswith("/" + suffix):
            return f
    return None


# -- baseline ---------------------------------------------------------------

def baseline_path(root: str) -> str:
    return os.path.join(root, "tools", "lint", "baseline.json")


def load_baseline(path: str) -> Dict[str, int]:
    """fingerprint -> grandfathered count; {} when absent/empty."""
    if not os.path.isfile(path):
        return {}
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    out: Dict[str, int] = {}
    for rec in doc.get("findings", []):
        out[rec["fingerprint"]] = out.get(rec["fingerprint"], 0) \
            + int(rec.get("count", 1))
    return out


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    counts: Dict[str, Dict[str, object]] = {}
    for f in sorted(findings, key=lambda f: (f.path, f.line)):
        rec = counts.setdefault(f.fingerprint, {
            "fingerprint": f.fingerprint, "rule": f.rule, "path": f.path,
            "message": f.message, "count": 0})
        rec["count"] = int(rec["count"]) + 1
    doc = {"schema": JSON_SCHEMA_VERSION,
           "findings": sorted(counts.values(),
                              key=lambda r: (r["path"], r["rule"],
                                             r["message"]))}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")


def split_new(findings: Sequence[Finding],
              baseline: Dict[str, int]) -> Tuple[List[Finding],
                                                 List[Finding]]:
    """(new, baselined): each fingerprint consumes its grandfathered
    count in (path, line) order; the overflow is new."""
    budget = dict(baseline)
    new: List[Finding] = []
    old: List[Finding] = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        if budget.get(f.fingerprint, 0) > 0:
            budget[f.fingerprint] -= 1
            old.append(f)
        else:
            new.append(f)
    return new, old


# -- driver helpers ---------------------------------------------------------

def parse_errors(files: Sequence[FileInfo]) -> List[Finding]:
    return [Finding(_PARSE_RULE, f.relpath, 1,
                    f"file does not parse: {f.parse_error}")
            for f in files if f.parse_error]


def apply_suppressions(files: Sequence[FileInfo],
                       findings: Sequence[Finding]
                       ) -> Tuple[List[Finding], List[Finding]]:
    """(kept, suppressed) after inline `# graftlint: disable=` marks."""
    by_path = {f.relpath: f for f in files}
    kept: List[Finding] = []
    dropped: List[Finding] = []
    for f in findings:
        fi = by_path.get(f.path)
        if fi is not None and fi.suppressed(f.line, f.rule):
            dropped.append(f)
        else:
            kept.append(f)
    return kept, dropped


def report_json(files: Sequence[FileInfo], new: Sequence[Finding],
                baselined: Sequence[Finding],
                suppressed: Sequence[Finding],
                rules: Sequence[str]) -> Dict[str, object]:
    return {
        "schema": JSON_SCHEMA_VERSION,
        "files_scanned": len(files),
        "rules": sorted(rules),
        "new": [f.to_dict() for f in new],
        "baselined": [f.to_dict() for f in baselined],
        "suppressed": [f.to_dict() for f in suppressed],
        "counts": {"new": len(new), "baselined": len(baselined),
                   "suppressed": len(suppressed)},
    }


def report_text(files: Sequence[FileInfo], new: Sequence[Finding],
                baselined: Sequence[Finding],
                suppressed: Sequence[Finding]) -> str:
    lines = [f.format() for f in
             sorted(new, key=lambda f: (f.path, f.line, f.rule))]
    lines.append(
        f"graftlint: {len(new)} new finding(s), "
        f"{len(baselined)} baselined, {len(suppressed)} suppressed, "
        f"{len(files)} files scanned")
    return "\n".join(lines)
