"""LGT005 — vocabulary drift.

Structured observability only works while the vocabulary is closed:
dashboards and the trace analyzer match on exact strings. One catalog
anchors it: `obs/events.py` EVENTS — every `log.event(kind, ...)` kind.
A kind missing from the catalog is either a typo (the event silently
never matches any consumer) or an undocumented addition.

Checks, when the catalog is present in the scanned set:

* literal `log.event("kind", ...)` kinds must be EVENTS keys;
* a NON-literal kind argument is flagged too — pass-through helpers
  (registry._note) carry an inline suppression plus the runtime
  `__debug__` validation in log.event, which is the dynamic half of
  this rule.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set

from ..core import FileInfo, Finding, find_file
from . import _common

RULE = "LGT005"
TITLE = "vocabulary drift"


def _catalog(files: List[FileInfo], suffix: str,
             var: str) -> Optional[Set[str]]:
    fi = find_file(files, suffix)
    if fi is None or fi.tree is None:
        return None
    node = _common.module_assign(fi.tree, var)
    if node is None:
        return None
    return _common.literal_str_elts(node)


def check(files: List[FileInfo]) -> List[Finding]:
    out: List[Finding] = []
    events = _catalog(files, "obs/events.py", "EVENTS")

    if events is not None:
        for fi in files:
            if fi.tree is None:
                continue
            for node in ast.walk(fi.tree):
                if not (isinstance(node, ast.Call) and
                        isinstance(node.func, ast.Attribute) and
                        node.func.attr == "event" and
                        isinstance(node.func.value, ast.Name) and
                        node.func.value.id == "log"):
                    continue
                if not node.args:
                    continue
                kind = _common.str_const(node.args[0])
                if kind is None:
                    out.append(Finding(
                        RULE, fi.relpath, node.lineno,
                        "non-literal log.event kind — lint cannot "
                        "check it against obs/events.py (suppress "
                        "with a reason if runtime validation covers "
                        "the pass-through)"))
                elif kind not in events:
                    out.append(Finding(
                        RULE, fi.relpath, node.lineno,
                        f"log.event kind {kind!r} is not in the "
                        f"obs/events.py catalog — typo, or an "
                        f"uncatalogued addition"))

    return out
