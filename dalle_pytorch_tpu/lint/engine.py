"""graftlint engine: file walking, pragma suppression, baseline, fixes.

The engine is jax-free and runs in milliseconds per file — it must stay
importable and fast on a bare CPU box (CI's lint job budget is seconds).
"""
from __future__ import annotations

import ast
import dataclasses
import io
import json
import re
import tokenize
import zlib
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .rules import RULES, FileCtx

# `# graftlint: disable=ENV001,DOT001 (reason why the rule does not apply)`
# — may trail other comment text (`# pragma: no cover — graftlint: ...`),
# but must end the line so the justification is unambiguous
_PRAGMA_RE = re.compile(
    r"graftlint:\s*disable=([A-Za-z0-9_]+(?:\s*,\s*[A-Za-z0-9_]+)*)"
    r"\s*(?:\((.*)\))?\s*$")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    rule: str
    line: int          # 1-based start line of the flagged statement
    col: int
    message: str
    line_text: str = ""
    end_line: int = 0  # 1-based end line (pragma scope for multi-line stmts)

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col + 1}: " \
               f"{self.rule} {self.message}"


def fingerprint(f: Finding) -> str:
    """Line-number-independent identity for baseline entries: file + rule +
    crc32 of the stripped source line, so unrelated edits above a baselined
    finding don't invalidate the baseline."""
    crc = zlib.crc32(f.line_text.strip().encode())
    return f"{f.path}::{f.rule}::{crc:08x}"


def _parse_pragmas(src: str) -> Tuple[Dict[int, Set[str]], List[Finding],
                                      List[Tuple[int, Set[str], str]]]:
    """Map line -> set of disabled rules, PRAGMA001 findings for pragmas
    missing the mandatory justification, and the justified pragma entries
    ``(line, rules, comment)`` themselves (for unused-suppression
    accounting)."""
    disabled: Dict[int, Set[str]] = {}
    errors: List[Finding] = []
    pragmas: List[Tuple[int, Set[str], str]] = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(src).readline)
        comments = [(t.start[0], t.string) for t in tokens
                    if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, IndentationError):
        return disabled, errors, pragmas
    for line, comment in comments:
        m = _PRAGMA_RE.search(comment)
        if not m:
            continue
        rules = {r.strip().upper() for r in m.group(1).split(",")}
        reason = (m.group(2) or "").strip()
        if not reason:
            errors.append(Finding(
                path="", rule="PRAGMA001", line=line, col=0,
                message="graftlint pragma without a justification: write "
                        "'# graftlint: disable=RULE (why the rule does not "
                        "apply here)'",
                line_text=comment, end_line=line))
            continue
        disabled.setdefault(line, set()).update(rules)
        pragmas.append((line, rules, comment))
    return disabled, errors, pragmas


def _suppressing_lines(f: Finding, disabled: Dict[int, Set[str]]) -> List[int]:
    """The pragma lines that suppress this finding: the line above the
    flagged statement, any line of the statement, or its first line."""
    lines = range(f.line - 1, max(f.end_line, f.line) + 1)
    return [ln for ln in lines
            if f.rule in disabled.get(ln, ()) or "ALL" in disabled.get(ln, ())]


def lint_source(src: str, path: str = "<string>",
                select: Optional[Iterable[str]] = None) -> List[Finding]:
    """Run the (selected) rules over one source string.  Returns findings
    with pragma suppression already applied; unsuppressable engine
    findings are included: PRAGMA001 (justification-less pragmas) and
    PRAGMA002 (justified pragmas that suppress nothing — stale
    suppressions outlive refactors and silently blind the rule they once
    excused; PRAGMA002 is only judged when every rule the pragma names was
    actually run, so ``--select`` subsets don't misreport)."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(path=path, rule="PARSE001", line=e.lineno or 1,
                        col=(e.offset or 1) - 1,
                        message=f"file does not parse: {e.msg}",
                        line_text="", end_line=e.lineno or 1)]
    lines = src.splitlines()
    ctx = FileCtx(path=path, tree=tree, lines=lines)
    disabled, pragma_errors, pragmas = _parse_pragmas(src)

    findings: List[Finding] = []
    selected = None if select is None else {r.upper() for r in select}
    rules = RULES if selected is None else {
        k: v for k, v in RULES.items() if k in selected}
    for rule_name, rule_fn in rules.items():
        for node, message in rule_fn(ctx):
            line = getattr(node, "lineno", 1)
            text = lines[line - 1] if 0 < line <= len(lines) else ""
            findings.append(Finding(
                path=path, rule=rule_name, line=line,
                col=getattr(node, "col_offset", 0), message=message,
                line_text=text,
                end_line=getattr(node, "end_lineno", line) or line))
    kept: List[Finding] = []
    used_pragma_lines: Set[int] = set()
    for f in findings:
        hit = _suppressing_lines(f, disabled)
        if hit:
            used_pragma_lines.update(hit)
        else:
            kept.append(f)
    for line, prules, comment in pragmas:
        if line in used_pragma_lines:
            continue
        judgeable = selected is None or (
            "ALL" not in prules and prules <= selected)
        if not judgeable:
            continue
        kept.append(Finding(
            path=path, rule="PRAGMA002", line=line, col=0,
            message=f"unused suppression: this pragma disables "
                    f"{','.join(sorted(prules))} but suppresses no finding "
                    "— the code it excused is gone; delete the pragma",
            line_text=comment, end_line=line))
    kept.extend(dataclasses.replace(e, path=path) for e in pragma_errors)
    return sorted(kept, key=lambda f: (f.line, f.col, f.rule))


_SKIP_DIRS = {"__pycache__", ".git", ".cache", "node_modules", ".venv"}


def iter_python_files(paths: Sequence[str]) -> List[Path]:
    out: List[Path] = []
    for p in paths:
        path = Path(p)
        if path.is_dir():
            out.extend(sorted(
                f for f in path.rglob("*.py")
                if not any(part in _SKIP_DIRS for part in f.parts)))
        elif path.suffix == ".py":
            out.append(path)
    return out


def lint_paths(paths: Sequence[str],
               select: Optional[Iterable[str]] = None) -> List[Finding]:
    findings: List[Finding] = []
    for f in iter_python_files(paths):
        findings.extend(lint_source(f.read_text(), path=str(f),
                                    select=select))
    return findings


# --- baseline ------------------------------------------------------------


def load_baseline(path) -> Set[str]:
    p = Path(path)
    if not p.exists():
        return set()
    data = json.loads(p.read_text())
    return set(data.get("suppressed", []))


def write_baseline(findings: Sequence[Finding], path) -> None:
    entries = sorted({fingerprint(f) for f in findings})
    Path(path).write_text(json.dumps(
        {"comment": "graftlint baseline — known findings grandfathered in; "
                    "regenerate with tools/graftlint.py --write-baseline",
         "suppressed": entries}, indent=2) + "\n")


def filter_baseline(findings: Sequence[Finding],
                    baseline: Set[str]) -> List[Finding]:
    return [f for f in findings if fingerprint(f) not in baseline]


def stale_baseline_entries(findings: Sequence[Finding],
                           baseline: Set[str]) -> List[str]:
    """Baseline fingerprints matching no current finding — each one is a
    fixed (or vanished) legacy finding whose grandfather entry now only
    risks masking a future regression at the same source line.  Pass the
    PRE-filter findings; prune with ``tools/graftlint.py
    --prune-baseline``."""
    live = {fingerprint(f) for f in findings}
    return sorted(baseline - live)


def prune_baseline(findings: Sequence[Finding], path) -> List[str]:
    """Rewrite the baseline at ``path`` keeping only fingerprints that
    still match a (pre-filter) finding; returns the dropped stale
    entries.  No-op when the file does not exist."""
    baseline = load_baseline(path)
    if not baseline:
        return []
    stale = stale_baseline_entries(findings, baseline)
    if stale:
        live = {fingerprint(f) for f in findings}
        Path(path).write_text(json.dumps(
            {"comment": "graftlint baseline — known findings grandfathered "
                        "in; regenerate with tools/graftlint.py "
                        "--write-baseline",
             "suppressed": sorted(baseline & live)}, indent=2) + "\n")
    return stale


# --- machine-readable output ---------------------------------------------

# The contract CI consumes (tests/test_graftlint.py validates emitted
# documents against this schema): bump "version" on breaking changes.
FINDINGS_JSON_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": ["tool", "version", "files_scanned", "counts", "findings"],
    "additionalProperties": False,
    "properties": {
        "tool": {"const": "graftlint"},
        "version": {"type": "integer", "minimum": 1},
        "files_scanned": {"type": "integer", "minimum": 0},
        "counts": {"type": "object",
                   "additionalProperties": {"type": "integer"}},
        "findings": {
            "type": "array",
            "items": {
                "type": "object",
                "required": ["path", "rule", "line", "col", "message",
                             "fingerprint"],
                "additionalProperties": False,
                "properties": {
                    "path": {"type": "string"},
                    "rule": {"type": "string", "pattern": "^[A-Z0-9_]+$"},
                    "line": {"type": "integer", "minimum": 1},
                    "col": {"type": "integer", "minimum": 0},
                    "message": {"type": "string"},
                    "fingerprint": {"type": "string"},
                },
            },
        },
    },
}


def findings_to_json(findings: Sequence[Finding],
                     files_scanned: int = 0) -> dict:
    """Findings as the JSON document FINDINGS_JSON_SCHEMA describes."""
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return {
        "tool": "graftlint",
        "version": 1,
        "files_scanned": files_scanned,
        "counts": counts,
        "findings": [
            {"path": f.path, "rule": f.rule, "line": f.line, "col": f.col,
             "message": f.message, "fingerprint": fingerprint(f)}
            for f in findings],
    }


def findings_to_sarif(findings: Sequence[Finding]) -> dict:
    """Findings as a minimal SARIF 2.1.0 log (the format code-scanning
    UIs ingest); fingerprints carry the baseline identity."""
    from .rules import RULES

    rule_ids = sorted({f.rule for f in findings} | set(RULES))
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "graftlint",
                "rules": [{"id": r} for r in rule_ids],
            }},
            "results": [{
                "ruleId": f.rule,
                "level": "error",
                "message": {"text": f.message},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": f.line,
                               "startColumn": f.col + 1},
                }}],
                "partialFingerprints": {"graftlint/v1": fingerprint(f)},
            } for f in findings],
        }],
    }


# --- ENV001 mechanical fix ----------------------------------------------

_ENV_IMPORT = "from dalle_pytorch_tpu.utils.helpers import env_flag"


def _env001_call_rewrite(node: ast.Call) -> Optional[str]:
    """env_flag replacement text for a fixable ENV001 call, else None.
    Fixable: single string-literal name, optionally with a falsy-constant
    default (None/''/False) — exactly the cases where env_flag(name) is
    the drop-in truth-equivalent."""
    if not node.args or node.keywords:
        return None
    name = node.args[0]
    if not (isinstance(name, ast.Constant) and isinstance(name.value, str)):
        return None
    if len(node.args) == 2:
        default = node.args[1]
        if not (isinstance(default, ast.Constant) and not default.value):
            return None
    elif len(node.args) != 1:
        return None
    return f'env_flag("{name.value}")'


def fix_env001(src: str, path: str = "<string>") -> Tuple[str, int]:
    """Mechanically rewrite fixable ENV001 truth-test calls to
    ``env_flag(NAME)``, adding the helpers import if the file doesn't
    already bind ``env_flag``.  Returns (new_source, fix_count)."""
    findings = lint_source(src, path=path, select=("ENV001",))
    tree = ast.parse(src)
    flagged = {(f.line, f.col) for f in findings if f.rule == "ENV001"}
    edits = []  # (lineno, col, end_lineno, end_col, replacement)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and (node.lineno, node.col_offset) in flagged:
            new = _env001_call_rewrite(node)
            if new is not None:
                edits.append((node.lineno, node.col_offset,
                              node.end_lineno, node.end_col_offset, new))
    if not edits:
        return src, 0

    lines = src.splitlines(keepends=True)
    applied = 0
    for l0, c0, l1, c1, new in sorted(edits, reverse=True):
        if l0 != l1:
            continue  # multi-line call: leave for a human
        line = lines[l0 - 1]
        lines[l0 - 1] = line[:c0] + new + line[c1:]
        applied += 1
    if not applied:
        return src, 0

    has_import = any(
        isinstance(n, ast.ImportFrom)
        and any(a.name == "env_flag" or a.asname == "env_flag"
                for a in n.names)
        for n in ast.walk(tree)) or "def env_flag" in src
    if not has_import:
        insert_at = 0
        for i, stmt in enumerate(tree.body):
            if isinstance(stmt, (ast.Import, ast.ImportFrom)):
                insert_at = stmt.end_lineno
            elif i == 0 and isinstance(stmt, ast.Expr) \
                    and isinstance(stmt.value, ast.Constant):
                insert_at = stmt.end_lineno  # module docstring
        lines.insert(insert_at, _ENV_IMPORT + "\n")
    return "".join(lines), applied
