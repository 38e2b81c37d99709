#!/usr/bin/env python
"""Run the jax-version-guarded device test modules with the guard stripped.

Some device test modules skip themselves outright on jax < 0.5 (jaxlib
0.4.x CPU segfaults *flakily* while tracing the device drivers' scan
bodies, and a mid-suite crash would abort the whole pytest run).  That
guard opened a silent tier-1 coverage hole on the pinned jax: a green
suite says nothing about the serving loop there.  This script closes it
the way PR 6 validated its changes — run the SAME tests from
guard-stripped copies, in their own pytest process so a (rare) tracer
segfault cannot take tier-1 down.

The module set is DISCOVERED: every ``tests/test_*.py`` carrying the
version-guard block is stripped and run, so new guarded device suites
(the r13 device-plane work added candidates) ride along without editing
this script.  Unguarded device tests (tests/test_pred_plane.py, the
table-plane oracle suite) already run in tier-1 on every pin and need no
stripping.

On jax >= 0.5 the guard is inactive and the regular suite already runs
the modules; the script exits 0 without duplicating the work (pass
``--force`` to run the stripped copies anyway).

Usage: make test-device-stripped  (or: python scripts/run_device_stripped.py)
"""

import glob
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GUARD = re.compile(
    r"^if tuple\(int\(x\) for x in jax\.__version__.*?\n(?:    .*\n|\)\n)*",
    re.MULTILINE,
)


def guarded_modules():
    found = []
    for path in sorted(glob.glob(os.path.join(REPO, "tests", "test_*.py"))):
        with open(path) as fh:
            src = fh.read()
        if GUARD.search(src):
            found.append((path, src))
    return found


def main() -> int:
    import jax

    guard_active = tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5)
    if not guard_active and "--force" not in sys.argv[1:]:
        print(
            f"jax {jax.__version__}: the version guard is inactive and the "
            "regular suite runs the guarded device modules — nothing to "
            "strip (pass --force to run the stripped copies anyway)"
        )
        return 0

    modules = guarded_modules()
    if not modules:
        print(
            "no tests/test_*.py carries the jax version-guard block: the "
            "guard moved — update scripts/run_device_stripped.py",
            file=sys.stderr,
        )
        return 2

    rc = 0
    for path, src in modules:
        stripped_src, hits = GUARD.subn("", src)
        if hits != 1:
            print(
                f"expected exactly one version-guard block in {path}, "
                f"found {hits}: update scripts/run_device_stripped.py",
                file=sys.stderr,
            )
            return 2
        # no test_ prefix: tier-1's directory collection must never pick
        # the copy up (only this script runs it, by explicit path)
        name = os.path.basename(path)[len("test_") :]
        stripped = os.path.join(REPO, "tests", f"_stripped_{name}")
        with open(stripped, "w") as fh:
            fh.write(stripped_src)
        try:
            rc = (
                subprocess.run(
                    [
                        sys.executable, "-m", "pytest", stripped, "-q",
                        "-p", "no:cacheprovider", "-p", "no:randomly",
                    ],
                    cwd=REPO,
                    env={**os.environ, "JAX_PLATFORMS": "cpu"},
                ).returncode
                or rc
            )
        finally:
            # never leave the copy behind: a crash of the child must not
            # turn into a stray module a later collection could import
            try:
                os.unlink(stripped)
            except OSError:
                pass
    return rc


if __name__ == "__main__":
    sys.exit(main())
