"""repro-lint core: findings, rule registry, suppressions, baseline, runner.

Stdlib-only on purpose (``ast`` + friends): the linter never imports the
code it scans, so it runs on a checkout with **no jax installed** — the CI
lint job asserts exactly that — and behaves identically whatever jax is
installed. Rules live in ``repro.analysis.lint.rules`` and register
themselves via :func:`register`; adding a rule is one module with one
class (see rules/__init__.py).

Finding format (one per line, ruff/gcc style, clickable in editors)::

    path:line: <rule-id> message

Suppression: a ``# repro-lint: disable=<rule>[,<rule>...]`` (or
``disable=all``) comment on the finding's line, or alone on the line
directly above it, silences the finding. Deliberate violations (e.g. the
one blessed host sync per decode step) carry a marker plus a one-line
justification; everything else is a lint failure.

Baseline: a checked-in file of line-number-free fingerprints
(``path|rule|message``) for grandfathered findings. The shipped baseline
is EMPTY — the policy is to fix the tree, not to grandfather — but the
mechanism exists so a future sweep that lands a new rule against old code
can ratchet instead of big-banging.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import re
from pathlib import Path, PurePosixPath
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set

__all__ = [
    "Finding", "Rule", "FileContext", "LintResult", "REGISTRY", "register",
    "lint_source", "lint_paths", "iter_py_files", "load_baseline",
    "baseline_lines",
]


@dataclasses.dataclass(frozen=True, order=True)
class Finding:
    """One rule violation at one source location."""
    path: str
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def fingerprint(self) -> str:
        """Line-number-free identity used by the baseline file (line
        numbers churn on every unrelated edit; path+rule+message is
        stable until the violation itself changes)."""
        return f"{self.path}|{self.rule}|{self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


class FileContext:
    """Everything a rule needs about one file: parsed tree, source lines,
    path split into parts (for scope checks), and the lazily-built module
    model shared by the trace-aware rules."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.parts = PurePosixPath(path.replace("\\", "/")).parts
        self._model = None

    @property
    def model(self):
        """ModuleModel (see modmodel.py), built once per file on first
        use by a trace-aware rule."""
        if self._model is None:
            from .modmodel import ModuleModel
            self._model = ModuleModel(self.tree)
        return self._model

    def in_dir(self, *names: str) -> bool:
        """True if any path component matches one of ``names`` — how
        rules scope themselves out of tests/ or benchmarks/."""
        return bool(set(self.parts[:-1]) & set(names))


class Rule:
    """Base class for lint rules. Subclasses set ``id``/``summary``,
    optionally ``skip_dirs`` (path components the rule never applies
    under), and implement ``check``."""

    id: str = ""
    summary: str = ""
    #: path components (directory names) this rule is scoped OUT of —
    #: e.g. retrace hazards only matter for code that serves traffic,
    #: so that rule skips tests/ and benchmarks/.
    skip_dirs: Sequence[str] = ()

    def applies_to(self, ctx: FileContext) -> bool:
        return not (self.skip_dirs and ctx.in_dir(*self.skip_dirs))

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        raise NotImplementedError
        yield  # pragma: no cover


#: rule-id -> Rule instance. Populated by importing the rules package.
REGISTRY: Dict[str, Rule] = {}


def register(cls):
    """Class decorator adding a rule to the registry (one instance,
    stateless between files)."""
    assert cls.id and cls.id not in REGISTRY, cls
    REGISTRY[cls.id] = cls()
    return cls


def _ensure_rules_loaded() -> None:
    if not REGISTRY:
        from . import rules  # noqa: F401  (import registers the rules)


# --------------------------------------------------------------------------
# Suppression comments
# --------------------------------------------------------------------------

_SUPPRESS_RE = re.compile(r"#\s*repro-lint:\s*disable=([A-Za-z0-9_\-, ]+)")


def suppressions(source_lines: List[str]) -> Dict[int, Set[str]]:
    """1-based line number -> set of suppressed rule ids ('all' wildcard
    included verbatim)."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source_lines, start=1):
        m = _SUPPRESS_RE.search(line)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _is_suppressed(f: Finding, sup: Dict[int, Set[str]],
                   lines: List[str]) -> bool:
    for ln in (f.line, f.line - 1):
        rules = sup.get(ln)
        if not rules:
            continue
        if ln != f.line:
            # a comment on the previous line only counts if that line is
            # comment-only — a trailing marker belongs to ITS statement
            prev = lines[ln - 1].strip() if 0 < ln <= len(lines) else ""
            if not prev.startswith("#"):
                continue
        if "all" in rules or f.rule in rules:
            return True
    return False


# --------------------------------------------------------------------------
# File discovery (gitignore-aware, no git needed)
# --------------------------------------------------------------------------

#: always skipped regardless of .gitignore — cache/VCS litter
ALWAYS_SKIP_DIRS = {
    "__pycache__", ".git", ".hg", ".svn", ".ruff_cache", ".pytest_cache",
    ".hypothesis", ".mypy_cache", ".venv", "venv", "node_modules",
}


def _gitignore_patterns(root: Path) -> tuple[Set[str], Set[str]]:
    """(dir names, file suffixes) from the root .gitignore — a deliberate
    subset of gitignore syntax covering what this repo uses: bare names /
    ``name/`` / ``**/name/`` become directory-name skips, ``*.ext``
    becomes a suffix skip. Negations and nested patterns are out of scope
    (the linter only needs to not descend into ignored litter)."""
    dirs: Set[str] = set()
    suffixes: Set[str] = set()
    gi = root / ".gitignore"
    if not gi.is_file():
        return dirs, suffixes
    for raw in gi.read_text().splitlines():
        pat = raw.strip()
        if not pat or pat.startswith("#") or pat.startswith("!"):
            continue
        if pat.startswith("**/"):
            pat = pat[3:]
        if pat.startswith("*."):
            suffixes.add(pat[1:])           # "*.pyc" -> ".pyc"
        elif "/" not in pat.rstrip("/"):
            dirs.add(pat.rstrip("/"))
    return dirs, suffixes


def iter_py_files(paths: Sequence[str],
                  root: Optional[Path] = None) -> Iterator[Path]:
    """Yield .py files under ``paths`` (files or directories), skipping
    __pycache__ / hidden caches / anything the root .gitignore names."""
    root = Path(root) if root is not None else Path.cwd()
    skip_dirs, skip_suffixes = _gitignore_patterns(root)
    skip_dirs |= ALWAYS_SKIP_DIRS

    def walk(p: Path) -> Iterator[Path]:
        if p.is_file():
            if p.suffix == ".py" and p.suffix not in skip_suffixes:
                yield p
            return
        if not p.is_dir():
            raise FileNotFoundError(f"no such file or directory: {p}")
        for child in sorted(p.iterdir()):
            if child.name in skip_dirs or child.name.startswith("."):
                continue
            yield from walk(child)

    for p in paths:
        yield from walk(Path(p))


# --------------------------------------------------------------------------
# Baseline
# --------------------------------------------------------------------------

def load_baseline(path: Optional[str]) -> Set[str]:
    """Fingerprint set from a baseline file; missing file = empty."""
    if not path or not Path(path).is_file():
        return set()
    out = set()
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if line and not line.startswith("#"):
            out.add(line)
    return out


def baseline_lines(findings: Iterable[Finding]) -> List[str]:
    return sorted({f.fingerprint() for f in findings})


# --------------------------------------------------------------------------
# Runner
# --------------------------------------------------------------------------

@dataclasses.dataclass
class LintResult:
    findings: List[Finding]          # live findings (fail the run)
    suppressed: int                  # silenced by inline markers
    baselined: int                   # silenced by the baseline file
    files: int                       # files scanned

    def to_json(self) -> dict:
        counts: Dict[str, int] = {}
        for f in self.findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        _ensure_rules_loaded()
        return {
            "version": 1,
            "tool": "repro-lint",
            "files": self.files,
            "suppressed": self.suppressed,
            "baselined": self.baselined,
            "counts": counts,
            "rules": {rid: r.summary for rid, r in sorted(REGISTRY.items())},
            "findings": [f.to_json() for f in sorted(self.findings)],
        }

    def render(self) -> str:
        return "\n".join(f.render() for f in sorted(self.findings))


def lint_source(source: str, path: str = "<string>",
                rules: Optional[Sequence[str]] = None) -> List[Finding]:
    """Lint one source string. Inline suppressions are honored; the
    baseline is not consulted (that's a repo-level concern). Unknown rule
    ids raise KeyError — a typo'd --rule must not silently pass."""
    _ensure_rules_loaded()
    active = [REGISTRY[r] for r in rules] if rules \
        else list(REGISTRY.values())
    try:
        ctx = FileContext(path, source)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, 0, "parse-error",
                        f"could not parse: {e.msg}")]
    sup = suppressions(ctx.lines)
    out: Set[Finding] = set()
    for rule in active:
        if not rule.applies_to(ctx):
            continue
        for f in rule.check(ctx):
            if not _is_suppressed(f, sup, ctx.lines):
                out.add(f)
    return sorted(out)


def _lint_file(path: Path, rules: Optional[Sequence[str]],
               rel_to: Path) -> tuple[List[Finding], int]:
    """(live findings, inline-suppressed count) for one file."""
    _ensure_rules_loaded()
    try:
        rel = str(path.relative_to(rel_to))
    except ValueError:
        rel = str(path)
    source = path.read_text()
    active = [REGISTRY[r] for r in rules] if rules \
        else list(REGISTRY.values())
    try:
        ctx = FileContext(rel, source)
    except SyntaxError as e:
        return [Finding(rel, e.lineno or 1, 0, "parse-error",
                        f"could not parse: {e.msg}")], 0
    sup = suppressions(ctx.lines)
    live: Set[Finding] = set()
    n_sup = 0
    for rule in active:
        if not rule.applies_to(ctx):
            continue
        for f in rule.check(ctx):
            if _is_suppressed(f, sup, ctx.lines):
                n_sup += 1
            else:
                live.add(f)
    return sorted(live), n_sup


def lint_paths(paths: Sequence[str], *,
               rules: Optional[Sequence[str]] = None,
               baseline: Optional[str] = None,
               root: Optional[Path] = None) -> LintResult:
    """Lint every .py file under ``paths``; the public entry the CLI and
    the tests share."""
    root = Path(root) if root is not None else Path.cwd()
    base = load_baseline(baseline)
    findings: List[Finding] = []
    n_sup = n_base = n_files = 0
    for p in iter_py_files(paths, root=root):
        n_files += 1
        live, sup = _lint_file(p, rules, rel_to=root)
        n_sup += sup
        for f in live:
            if f.fingerprint() in base:
                n_base += 1
            else:
                findings.append(f)
    return LintResult(sorted(findings), n_sup, n_base, n_files)
