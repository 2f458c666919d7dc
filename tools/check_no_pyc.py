"""Repo-hygiene guard: no compiled-python artifacts or tool caches may be
git-tracked.

``__pycache__``/``*.pyc`` files were purged from the tree once (PR 5) and
are gitignored, but an ignore rule cannot protect files that are ALREADY
tracked (``git add -f``, a rename that outruns the rule, an overeager
``git add .`` before .gitignore existed in a branch). This check makes the
invariant enforceable: it asks git for the tracked file list and fails on
any bytecode artifact. Stdlib only (runs in the CI docs job before any
heavy dependency is installed; also enforced by tests/test_docs.py).

  python tools/check_no_pyc.py [root]
"""
from __future__ import annotations

import subprocess
import sys

BAD_SUFFIXES = (".pyc", ".pyo", ".pyd")
# bytecode, hypothesis's example database, JAX's compilation cache
BAD_DIRS = ("__pycache__", ".hypothesis", ".jax_cache")


def tracked_artifacts(root: str) -> list:
    out = subprocess.run(["git", "ls-files", "-z"], cwd=root,
                         capture_output=True, check=True).stdout
    bad = []
    for path in out.decode().split("\0"):
        if not path:
            continue
        if (path.endswith(BAD_SUFFIXES)
                or any(d in path.split("/") for d in BAD_DIRS)):
            bad.append(path)
    return bad


def main(argv=None) -> int:
    root = (argv or sys.argv[1:] or ["."])[0]
    bad = tracked_artifacts(root)
    if bad:
        print("git-tracked bytecode or cache artifacts (purge with "
              "`git rm -r --cached <path>`):")
        for p in bad:
            print(f"  {p}")
        return 1
    print("no tracked bytecode or cache artifacts")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
