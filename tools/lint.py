#!/usr/bin/env python3
"""CLI entry point for skyanalyze (tools/analysis) — the
dependency-free AST static analyzer that replaced the original
regex linter. Same invocation format.sh and tests/test_lint.py have
always used; exit 0 = clean.

    python tools/lint.py                    full tree, human output
    python tools/lint.py path [path ...]    file passes on those paths
    python tools/lint.py --json OUT.json    also write the JSON
                                            artifact
    python tools/lint.py --write-env-docs   regenerate docs/env_vars.md
                                            from the env registry

Passes (catalog + noqa grammar: docs/static_analysis.md):
  * the nine rules ported from the regex linter — unused-import,
    whitespace, print-call, loop-host-sync, clock-injection,
    qos-admission, kernel-dispatch, sqlite-discipline, except-pass —
    plus the syntax gate;
  * lock-discipline — attributes written under a class's lock are
    never accessed lock-free (the PR 7/9 review-race class);
  * async-blocking — no time.sleep / sync HTTP / sqlite / file I/O
    on the serve/infer event loops;
  * tracer-safety — functions reachable from jax.jit / pallas_call /
    the dispatch ladder stay tracer-pure;
  * env-registry — every SKYT_* read resolves through
    utils/env.py, and docs/env_vars.md is generated + fresh;
  * registry-consistency — fault points, metric families, and
    JobStatus terminal states match their docs catalogs.

Project-wide passes (the last three) run only in full-tree mode (no
explicit path arguments) — linting one file stays fast and local.
"""
import sys
from pathlib import Path

_HERE = Path(__file__).resolve().parent
_REPO = _HERE.parent
if str(_HERE) not in sys.path:
    sys.path.insert(0, str(_HERE))

from analysis import core as _core          # noqa: E402


def check_file(path):
    """Single-file API kept for tests/test_lint.py: formatted issue
    strings from every file-scoped pass."""
    return _core.check_file(path)


def write_env_docs() -> Path:
    """Regenerate docs/env_vars.md from the env registry."""
    from analysis import env_registry
    mod = env_registry._load_registry(
        _REPO / 'skypilot_tpu' / 'utils' / 'env.py')
    out = _REPO / 'docs' / 'env_vars.md'
    out.write_text(mod.generate_docs(), encoding='utf-8')
    return out


def main(argv):
    json_path = None
    roots = []
    args = list(argv)
    while args:
        a = args.pop(0)
        if a == '--json':
            if not args:
                print('--json needs an output path')
                return 2
            json_path = args.pop(0)
        elif a == '--write-env-docs':
            path = write_env_docs()
            print(f'wrote {path}')
            return 0
        else:
            roots.append(a)

    # Explicit paths = file passes only; full default tree = file +
    # project passes, rooted at the repo (independent of cwd).
    if roots:
        root, project = Path('.'), False
        if any(Path(r).is_absolute() for r in roots):
            root = Path('/')
            roots = [str(Path(r).resolve().relative_to(root))
                     for r in roots]
    else:
        root, project = _REPO, True
        if Path.cwd() == _REPO:
            root = Path('.')
    violations = _core.analyze(root, roots or None,
                               project_passes=project)
    files = _core.count_files(root, roots or None)
    for v in violations:
        print(v.format())
    print(f'{files} files checked, {len(violations)} issue(s)')
    if json_path:
        Path(json_path).write_text(
            _core.render_json(violations, files), encoding='utf-8')
    return 1 if violations else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
