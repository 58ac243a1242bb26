"""skyanalyze framework: pass registry, noqa grammar, runners, output.

Design rules (mirror tools/lint.py's original constraints):
  * stdlib only — the image ships no ruff/pylint/mypy;
  * every file is read + parsed exactly once per run (FileContext),
    shared by all passes;
  * suppression is handled HERE, not in passes: a pass reports every
    violation it sees and the framework drops the suppressed ones, so
    noqa semantics are uniform across all passes.

noqa grammar (docs/static_analysis.md):
  # noqa                      suppress every pass on this line
  # noqa: free text reason    same (no token is a known pass id)
  # noqa: lock-discipline     suppress exactly the named pass(es)
  # noqa: a, b                comma/space separated pass ids
"""
import ast
import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set


@dataclasses.dataclass(frozen=True)
class Violation:
    """One finding. ``path`` is the path as given (repo-relative when
    run via lint.py), ``line`` is 1-based (0 = whole file)."""
    path: str
    line: int
    pass_id: str
    message: str

    def format(self) -> str:
        return f'{self.path}:{self.line}: {self.message} ' \
               f'[{self.pass_id}]'

    def as_dict(self) -> Dict[str, object]:
        return {'path': self.path, 'line': self.line,
                'pass': self.pass_id, 'message': self.message}


class FileContext:
    """One parsed source file, shared by every file pass."""

    def __init__(self, path: Path, src: Optional[str] = None) -> None:
        self.path = path
        self.rel = path.as_posix()
        self.src = path.read_text(encoding='utf-8') \
            if src is None else src
        self.lines = self.src.splitlines()
        self.tree: Optional[ast.AST] = None
        self.syntax_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(self.src, filename=str(path))
        except SyntaxError as e:
            self.syntax_error = e

    def line_at(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ''


_NOQA_RE = re.compile(r'#\s*noqa\b(?::\s*(?P<args>.*))?', re.I)


def noqa_suppresses(line: str, pass_id: str,
                    known_ids: Set[str]) -> bool:
    """Does a ``# noqa`` comment on ``line`` suppress ``pass_id``?"""
    m = _NOQA_RE.search(line)
    if not m:
        return False
    args = (m.group('args') or '').strip()
    if not args:
        return True                      # bare noqa: everything
    tokens = {t.strip() for t in re.split(r'[,\s]+', args) if t.strip()}
    named = tokens & known_ids
    if not named:
        return True                      # free-text reason: everything
    return pass_id in named


class Pass:
    """Base class. File passes implement run(ctx); project passes set
    scope = 'project' and implement run_project(project)."""

    id = ''
    title = ''
    scope = 'file'

    def applies(self, ctx: FileContext) -> bool:
        return True

    def run(self, ctx: FileContext) -> List[Violation]:
        raise NotImplementedError

    def run_project(self, project: 'Project') -> List[Violation]:
        raise NotImplementedError


class Project:
    """Whole-tree view for project passes: every FileContext plus the
    repo root (for docs/). Tests point ``root`` at fixture trees."""

    def __init__(self, root: Path,
                 files: Sequence[FileContext]) -> None:
        self.root = root
        self.files = list(files)

    def doc(self, rel: str) -> Optional[str]:
        p = self.root / rel
        try:
            return p.read_text(encoding='utf-8')
        except OSError:
            return None


def _registry() -> List[Pass]:
    # Imported lazily so `import analysis.core` never cycles.
    from . import async_blocking, env_registry, lock_discipline, \
        metric_cardinality, ported, registry_consistency, tracer_safety
    return (ported.PASSES +
            [lock_discipline.LockDisciplinePass(),
             async_blocking.AsyncBlockingPass(),
             tracer_safety.TracerSafetyPass(),
             env_registry.EnvReadPass(),
             env_registry.EnvRegistryDriftPass(),
             metric_cardinality.MetricCardinalityPass(),
             registry_consistency.RegistryConsistencyPass()])


_PASSES: Optional[List[Pass]] = None


def all_passes() -> List[Pass]:
    global _PASSES
    if _PASSES is None:
        _PASSES = _registry()
    return _PASSES


def known_ids() -> Set[str]:
    return {p.id for p in all_passes()} | {'syntax'}


def _filter_noqa(violations: List[Violation],
                 ctx_by_rel: Dict[str, FileContext]) -> List[Violation]:
    ids = known_ids()
    out = []
    for v in violations:
        ctx = ctx_by_rel.get(v.path)
        if ctx is not None and v.line > 0 and noqa_suppresses(
                ctx.line_at(v.line), v.pass_id, ids):
            continue
        out.append(v)
    return out


def run_file_passes(ctx: FileContext) -> List[Violation]:
    if ctx.syntax_error is not None:
        e = ctx.syntax_error
        return [Violation(ctx.rel, e.lineno or 0, 'syntax',
                          f'syntax error: {e.msg}')]
    out: List[Violation] = []
    for p in all_passes():
        if p.scope != 'file' or not p.applies(ctx):
            continue
        out.extend(p.run(ctx))
    return _filter_noqa(out, {ctx.rel: ctx})


def check_file(path) -> List[str]:
    """Single-file compatibility API (tests/test_lint.py): run every
    file pass on one file, return formatted issue strings."""
    ctx = FileContext(Path(path))
    return [v.format() for v in run_file_passes(ctx)]


def analyze(root: Path, roots: Optional[Sequence[str]] = None,
            project_passes: bool = True) -> List[Violation]:
    """Full run: file passes over every .py under ``roots`` (given
    relative to ``root``), then project passes over the whole view.
    Returns violations sorted by (path, line, pass)."""
    roots = list(roots) if roots else [
        'skypilot_tpu', 'tests', 'tools', '__graft_entry__.py']
    files: List[FileContext] = []
    for r in roots:
        p = root / r
        if p.is_dir():
            files += [FileContext(f) for f in sorted(p.rglob('*.py'))
                      if '__pycache__' not in str(f)]
        elif p.exists():
            files.append(FileContext(p))
    ctx_by_rel = {c.rel: c for c in files}
    out: List[Violation] = []
    for ctx in files:
        out.extend(run_file_passes(ctx))
    if project_passes:
        project = Project(root, files)
        pv: List[Violation] = []
        for p in all_passes():
            if p.scope == 'project':
                pv.extend(p.run_project(project))
        out.extend(_filter_noqa(pv, ctx_by_rel))
    out.sort(key=lambda v: (v.path, v.line, v.pass_id, v.message))
    return out


def count_files(root: Path,
                roots: Optional[Sequence[str]] = None) -> int:
    roots = list(roots) if roots else [
        'skypilot_tpu', 'tests', 'tools', '__graft_entry__.py']
    n = 0
    for r in roots:
        p = root / r
        if p.is_dir():
            n += sum(1 for f in p.rglob('*.py')
                     if '__pycache__' not in str(f))
        elif p.exists():
            n += 1
    return n


def render_json(violations: List[Violation], files_checked: int) -> str:
    """Stable JSON artifact (tests/test_analysis.py goldens the
    schema)."""
    payload = {
        'schema': 1,
        'tool': 'skyanalyze',
        'files_checked': files_checked,
        'passes': sorted(known_ids()),
        'violations': [v.as_dict() for v in violations],
    }
    return json.dumps(payload, indent=2, sort_keys=True) + '\n'
