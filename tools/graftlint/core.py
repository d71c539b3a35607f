"""graftlint core — rule registry, suppressions, baseline, runner, reporters.

The framework half of tools/graftlint: rules (tools/graftlint/rules/) are
AST passes registered here; the runner walks the repo, applies per-line
``# graftlint: disable=<rule>`` suppressions, and splits findings into
new / baselined / stale against the checked-in baseline
(tools/graftlint/baseline.json).  HLO-contract helpers live separately in
tools/graftlint/hlo_contracts.py — they check compiled programs, not
source files, and are wired as tier-1 tests rather than repo-walk rules.

Design contract (docs/tutorials/static_analysis.md):
- a rule fires on the hazard LINE so a one-line suppression comment can
  acknowledge exactly one finding;
- fingerprints hash (path, rule, stripped line text, occurrence index) so
  baselined findings survive unrelated line moves but expire when the
  offending line changes;
- real violations get FIXED; the baseline is for load-bearing exceptions
  only, each entry carrying a ``note`` saying why it stays.
"""
import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_ROOTS = ("deepspeed_tpu", "tools", "tests")
DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "baseline.json")

_SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*disable=([\w,\-]+)")


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    rule: str
    path: str          # repo-relative, forward slashes
    line: int          # 1-based
    message: str
    col: int = 0

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class Rule:
    """Base class for AST rules.

    Subclasses set ``name`` (kebab-case, the suppression token), a one-line
    ``description`` for the catalog, optionally ``scopes`` (repo-relative
    path prefixes the rule applies to; None = everywhere), and implement
    ``check(tree, source, path) -> [Finding]``.  Suppression comments are
    handled by the runner, not the rule.
    """
    name: str = ""
    description: str = ""
    scopes: Optional[Sequence[str]] = None

    def applies_to(self, path: str) -> bool:
        if self.scopes is None:
            return True
        # out-of-repo paths (explicitly passed files) have no tree context
        # to scope by — a user linting one file wants the full catalog
        if os.path.isabs(path) or path.startswith(".."):
            return True
        return any(path == s or path.startswith(s.rstrip("/") + "/")
                   for s in self.scopes)

    def check(self, tree: ast.AST, source: str, path: str) -> List[Finding]:
        raise NotImplementedError


REGISTRY: Dict[str, Rule] = {}


def register(cls):
    """Class decorator: instantiate and register a Rule by its name."""
    rule = cls()
    assert rule.name, f"{cls.__name__} must set a rule name"
    assert rule.name not in REGISTRY, f"duplicate rule {rule.name!r}"
    REGISTRY[rule.name] = rule
    return cls


def _load_rules():
    """Import the rules package (registers every rule) exactly once."""
    if not REGISTRY:
        from . import rules  # noqa: F401
    return list(REGISTRY.values())


def _suppressed(finding: Finding, lines: Sequence[str]) -> bool:
    """True when the finding's line (or the line above, for wrapped
    statements) carries ``# graftlint: disable=<rule>[,<rule>...]``."""
    for ln in (finding.line, finding.line - 1):
        if 1 <= ln <= len(lines):
            m = _SUPPRESS_RE.search(lines[ln - 1])
            if m:
                names = {n.strip() for n in m.group(1).split(",")}
                if finding.rule in names or "all" in names:
                    return True
    return False


def run_source(source: str, path: str = "<string>",
               rules: Optional[Sequence[Rule]] = None,
               honor_suppressions: bool = True) -> List[Finding]:
    """Run rules over one file's source text; returns surviving findings.

    Syntax errors surface as a single pseudo-finding so a broken file
    cannot silently drop out of the lint.
    """
    if rules is None:
        rules = _load_rules()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(rule="syntax", path=path, line=e.lineno or 0,
                        message=f"syntax error: {e.msg}")]
    lines = source.splitlines()
    out: List[Finding] = []
    for rule in rules:
        if not rule.applies_to(path):
            continue
        for f in rule.check(tree, source, path):
            if honor_suppressions and _suppressed(f, lines):
                continue
            out.append(f)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


def _resolve_root(root: str, repo_root: str) -> str:
    """Absolute path for a lint root.  Relative roots try the caller's
    cwd first, then the repo root (the defaults resolve that way no
    matter where graftlint is invoked from).  A root that exists in
    NEITHER raises instead of silently walking nothing — an empty scan
    feeding --baseline-update would wipe the baseline."""
    if os.path.isabs(root):
        if not os.path.exists(root):
            raise FileNotFoundError(f"lint root {root!r} does not exist")
        return root
    for base in (os.getcwd(), repo_root):
        cand = os.path.join(base, root)
        if os.path.exists(cand):
            return cand
    raise FileNotFoundError(
        f"lint root {root!r} not found under {os.getcwd()} or {repo_root}")


def iter_py_files(roots: Sequence[str], repo_root: str = REPO_ROOT):
    """Yield repo-relative .py paths under ``roots`` (files or dirs)."""
    for root in roots:
        abs_root = _resolve_root(root, repo_root)
        if os.path.isfile(abs_root):
            yield os.path.relpath(abs_root, repo_root).replace(os.sep, "/")
            continue
        for dirpath, dirnames, names in os.walk(abs_root):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in ("__pycache__", ".git"))
            for name in sorted(names):
                if name.endswith(".py"):
                    yield os.path.relpath(
                        os.path.join(dirpath, name),
                        repo_root).replace(os.sep, "/")


def fingerprint(finding: Finding, line_text: str, occurrence: int) -> str:
    """Stable id for baselining: survives pure line-number moves, expires
    when the offending line's text changes.  ``occurrence`` disambiguates
    identical lines flagged by the same rule in one file."""
    key = f"{finding.path}|{finding.rule}|{line_text.strip()}|{occurrence}"
    return hashlib.sha1(key.encode()).hexdigest()[:16]


@dataclass
class RunResult:
    new: List[Finding] = field(default_factory=list)
    baselined: List[Finding] = field(default_factory=list)
    stale: List[dict] = field(default_factory=list)   # baseline entries
    fingerprints: Dict[str, Finding] = field(default_factory=dict)
    # coverage of this run: a baseline entry is only judged (stale) or
    # rewritten (on save) when its file was scanned AND its rule ran —
    # scoped runs must not eat out-of-scope baseline entries
    scanned_paths: set = field(default_factory=set)
    rule_names: set = field(default_factory=set)

    def covers(self, entry: dict) -> bool:
        return entry.get("path") in self.scanned_paths \
            and entry.get("rule") in self.rule_names

    @property
    def exit_code(self) -> int:
        return 1 if self.new else 0


def load_baseline(path: str = DEFAULT_BASELINE) -> dict:
    if not os.path.exists(path):
        return {"version": 1, "entries": []}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    assert isinstance(data.get("entries"), list), \
        f"malformed baseline {path}: no 'entries' list"
    return data


def save_baseline(result: RunResult, path: str = DEFAULT_BASELINE,
                  notes: Optional[Dict[str, str]] = None) -> dict:
    """Write every current finding (new + still-valid baselined) as the
    fresh baseline; stale COVERED entries are pruned, while entries the
    run did not cover (file outside the scanned roots, or rule not run)
    are preserved untouched — a scoped ``--baseline-update`` must not
    delete the rest of the repo's baseline.  ``notes`` maps fingerprint
    -> justification comment; notes on surviving entries are preserved."""
    old = load_baseline(path)["entries"]
    old_notes = {e["fingerprint"]: e.get("note", "") for e in old}
    entries = [e for e in old if not result.covers(e)]
    for fp, f in sorted(result.fingerprints.items(),
                        key=lambda kv: (kv[1].path, kv[1].line, kv[1].rule)):
        note = (notes or {}).get(fp) or old_notes.get(fp, "")
        entries.append({"fingerprint": fp, "rule": f.rule, "path": f.path,
                        "line": f.line, "message": f.message, "note": note})
    entries.sort(key=lambda e: (e["path"], e["line"], e["rule"]))
    data = {"version": 1, "entries": entries}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    return data


def run_paths(roots: Sequence[str] = DEFAULT_ROOTS,
              rules: Optional[Sequence[Rule]] = None,
              baseline_path: str = DEFAULT_BASELINE,
              repo_root: str = REPO_ROOT,
              use_baseline: bool = True) -> RunResult:
    """Lint the repo: walk ``roots``, run rules, partition findings
    against the baseline."""
    if rules is None:
        rules = _load_rules()
    result = RunResult(rule_names={r.name for r in rules})
    seen_occ: Dict[tuple, int] = {}
    for rel in iter_py_files(roots, repo_root):
        result.scanned_paths.add(rel)
        with open(os.path.join(repo_root, rel), encoding="utf-8") as f:
            source = f.read()
        lines = source.splitlines()
        for finding in run_source(source, rel, rules):
            text = lines[finding.line - 1] \
                if 1 <= finding.line <= len(lines) else ""
            k = (finding.path, finding.rule, text.strip())
            occ = seen_occ.get(k, 0)
            seen_occ[k] = occ + 1
            result.fingerprints[fingerprint(finding, text, occ)] = finding
    baseline = load_baseline(baseline_path) if use_baseline \
        else {"entries": []}
    known = {e["fingerprint"]: e for e in baseline["entries"]}
    for fp, f in result.fingerprints.items():
        (result.baselined if fp in known else result.new).append(f)
    live = set(result.fingerprints)
    # only entries this run COVERED can be judged gone; out-of-scope
    # entries are neither stale nor (on save) pruned
    result.stale = [e for e in baseline["entries"]
                    if e["fingerprint"] not in live and result.covers(e)]
    result.new.sort(key=lambda f: (f.path, f.line, f.rule))
    result.baselined.sort(key=lambda f: (f.path, f.line, f.rule))
    return result


# ---------------------------------------------------------------------------
# reporters
# ---------------------------------------------------------------------------

def report_text(result: RunResult, rules: Sequence[Rule]) -> str:
    out = []
    for f in result.new:
        out.append(f.format())
    for f in result.baselined:
        out.append(f"{f.format()}  (baselined)")
    for e in result.stale:
        out.append(f"graftlint: stale baseline entry "
                   f"{e['path']}:{e['line']} [{e['rule']}] — violation gone; "
                   f"run --baseline-update to prune")
    out.append(f"graftlint: {len(result.new)} new, "
               f"{len(result.baselined)} baselined, "
               f"{len(result.stale)} stale baseline "
               f"({len(rules)} rules)")
    return "\n".join(out)


def report_json(result: RunResult, rules: Sequence[Rule]) -> str:
    def enc(f: Finding):
        return {"rule": f.rule, "path": f.path, "line": f.line,
                "message": f.message}

    return json.dumps({
        "version": 1,
        "rules": sorted(r.name for r in rules),
        "new": [enc(f) for f in result.new],
        "baselined": [enc(f) for f in result.baselined],
        "stale_baseline": result.stale,
        "summary": {"new": len(result.new),
                    "baselined": len(result.baselined),
                    "stale_baseline": len(result.stale)},
    }, indent=2, sort_keys=True)


# ---------------------------------------------------------------------------
# shared AST helpers for rules
# ---------------------------------------------------------------------------

def call_name(node: ast.AST) -> Optional[str]:
    """Terminal name of a Call's func: ``jax.lax.psum`` -> 'psum',
    ``device_get`` -> 'device_get'; None for subscripts/lambdas."""
    if isinstance(node, ast.Call):
        node = node.func
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def contains_call_to(tree: ast.AST, names) -> bool:
    return any(isinstance(n, ast.Call) and call_name(n) in names
               for n in ast.walk(tree))


def string_constants(tree: ast.AST):
    """Every literal string in the subtree, including f-string parts."""
    for n in ast.walk(tree):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n.value


def walk_function_bodies(tree: ast.AST):
    """Yield every (Async)FunctionDef in the module, outermost first."""
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield n
