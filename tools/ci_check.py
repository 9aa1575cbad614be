"""CI gate: compare a pytest junit report against the seed-failure baseline.

The seed repo ships with known-failing tests (tests/seed_failures.txt,
one pytest node id per line, '#' comments allowed). CI fails on:

  * a test failing that is NOT in the baseline (new failure),
  * a baseline entry that now PASSES (stale baseline — the ratchet:
    fixes must be banked by trimming the baseline, or they can silently
    regress later),
  * --min-passed N given and fewer than N tests passed (full-tier runs),
  * tracked build/test artifacts in the git index — Python bytecode
    (__pycache__ / *.pyc), junit XML (report.xml, *.junit.xml),
    bench scratch outputs (BENCH_serving_{mixed,nightly}.json; the
    committed BENCH_serving.json BASELINE is exempt), replayable
    workload traces (*.trace.npz), and observability exports
    (*.trace.json / *.perfetto.json Perfetto timelines,
    metrics_snapshot.{json,prom} dumps from tools/export_trace.py),
    the repro-lint findings report (repro_lint_report.json from
    `python -m tools.analysis --report`), and JAX's compile cache
    (.jax_cache/) must never be committed
    (bytecode was once, by accident; .gitignore plus this gate keeps
    all of them out).

A second, sibling ratchet lives in tools/analysis: repro-lint
suppressions bank against tools/analysis/suppressions.txt with the
same stale-entry / --update-baseline semantics as the seed-failure
baseline here.

Baseline entries that still fail never block. Entries absent from the
report (e.g. @slow tests deselected in the fast tier) are ignored.

Ratchet workflow — when a PR fixes a known seed failure:

  1. CI (or a local run) fails with "stale baseline" naming the entries.
  2. Regenerate the report and rewrite the baseline in one step:

       PYTHONPATH=src python -m pytest -q --junitxml=report.xml || true
       python tools/ci_check.py report.xml tests/seed_failures.txt \
           --update-baseline

     --update-baseline removes exactly the now-passing entries (comments
     and still-failing/not-run entries are preserved) and exits 0.
  3. Commit the trimmed tests/seed_failures.txt with the fix.

NEW failures are never added to the baseline by this tool — fix them.

Usage:
  python -m pytest -q --junitxml=report.xml || true
  python tools/ci_check.py report.xml tests/seed_failures.txt \
      [--min-passed N] [--update-baseline]
"""
from __future__ import annotations

import argparse
import subprocess
import sys
import xml.etree.ElementTree as ET


def _is_artifact(path: str) -> bool:
    """Build/test artifacts that must never sit in the git index:
    bytecode, junit XML reports, bench scratch outputs, replayable
    trace files (serving_bench --skew regenerates *.trace.npz from a
    seeded spec every run), and observability exports (Perfetto
    *.trace.json / *.perfetto.json timelines and the
    metrics_snapshot.{json,prom} dumps, regenerated by
    tools/export_trace.py every nightly run). The committed
    BENCH_serving.json baseline is NOT an artifact — only the
    *_mixed/*_nightly scratch files CI regenerates every run are."""
    if "__pycache__" in path or path.endswith((".pyc", ".pyo")):
        return True
    if path.startswith(".jax_cache/"):
        # JAX's persistent compile cache (repro.launch.serve.
        # enable_compile_cache), rebuilt by every machine that compiles
        return True
    name = path.rsplit("/", 1)[-1]
    if name == "report.xml" or name.endswith(".junit.xml"):
        return True
    if name.startswith("junit") and name.endswith(".xml"):
        return True
    if name.endswith(".trace.npz"):
        return True
    if name.endswith((".trace.json", ".perfetto.json")):
        return True
    if name.startswith("metrics_snapshot") and name.endswith(
            (".json", ".prom")):
        return True
    if name == "repro_lint_report.json":
        # repro-lint findings report (python -m tools.analysis --report),
        # regenerated every nightly run; only the suppression BASELINE
        # (tools/analysis/suppressions.txt) is committed
        return True
    return name.startswith("BENCH_") and (
        name.endswith("_mixed.json") or name.endswith("_nightly.json")
    )


def tracked_artifacts() -> list:
    """Tracked artifact paths (empty when clean or when git is
    unavailable — e.g. running from an exported tarball)."""
    try:
        out = subprocess.run(
            ["git", "ls-files"], capture_output=True, text=True, check=True
        ).stdout
    except (OSError, subprocess.CalledProcessError):
        return []
    return [p for p in out.splitlines() if _is_artifact(p)]


def node_id(case: ET.Element) -> str:
    """Reconstruct the pytest node id from a junit <testcase>."""
    cls = case.get("classname") or ""
    name = case.get("name") or ""
    if not cls:
        return name
    return cls.replace(".", "/") + ".py::" + name


def collect(report_path: str):
    root = ET.parse(report_path).getroot()
    suites = root.iter("testsuite") if root.tag == "testsuites" else [root]
    passed, failed, skipped = [], [], []
    for suite in suites:
        for case in suite.iter("testcase"):
            nid = node_id(case)
            if case.find("failure") is not None or case.find("error") is not None:
                failed.append(nid)
            elif case.find("skipped") is not None:
                skipped.append(nid)
            else:
                passed.append(nid)
    return passed, failed, skipped


def rewrite_baseline(path: str, stale: set) -> None:
    """Drop now-passing entries; keep comments, order, and every entry
    that still fails or was not run in this report."""
    with open(path) as f:
        lines = f.readlines()
    kept = [ln for ln in lines if ln.strip() not in stale]
    with open(path, "w") as f:
        f.writelines(kept)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("report")
    ap.add_argument("baseline")
    ap.add_argument("--min-passed", type=int, default=0,
                    help="fail if fewer tests passed (full-tier regression floor)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline dropping entries that now "
                         "pass (the ratchet), instead of failing on them")
    args = ap.parse_args(argv)

    with open(args.baseline) as f:
        baseline = {
            line.strip() for line in f
            if line.strip() and not line.startswith("#")
        }
    passed, failed, skipped = collect(args.report)
    known = [nid for nid in failed if nid in baseline]
    new = [nid for nid in failed if nid not in baseline]
    fixed = sorted(baseline & set(passed))

    print(f"[ci_check] {len(passed)} passed, {len(failed)} failed "
          f"({len(known)} known / {len(new)} new), {len(skipped)} skipped")

    rc = 0
    if fixed:
        if args.update_baseline:
            rewrite_baseline(args.baseline, set(fixed))
            print(f"[ci_check] baseline updated: {len(fixed)} fixed "
                  f"entr{'y' if len(fixed) == 1 else 'ies'} removed from "
                  f"{args.baseline}:")
            for nid in fixed:
                print(f"  trimmed: {nid}")
        else:
            print(f"[ci_check] FAIL: stale baseline — {len(fixed)} "
                  f"entr{'y' if len(fixed) == 1 else 'ies'} now PASS. "
                  f"Bank the fix: rerun with --update-baseline and commit "
                  f"{args.baseline}:")
            for nid in fixed:
                print(f"  stale: {nid}")
            rc = 1
    if new:
        print(f"[ci_check] FAIL: {len(new)} new failure(s) vs seed baseline:")
        for nid in sorted(new):
            print(f"  NEW: {nid}")
        rc = 1
    if args.min_passed and len(passed) < args.min_passed:
        print(f"[ci_check] FAIL: only {len(passed)} passed "
              f"< required floor {args.min_passed}")
        rc = 1
    tracked = tracked_artifacts()
    if tracked:
        print(f"[ci_check] FAIL: {len(tracked)} tracked artifact path(s) "
              f"(bytecode / junit XML / bench scratch) — git rm --cached "
              f"them (they are .gitignore'd):")
        for p in tracked[:10]:
            print(f"  tracked: {p}")
        if len(tracked) > 10:
            print(f"  ... and {len(tracked) - 10} more")
        rc = 1
    if rc == 0:
        print("[ci_check] OK: no regressions vs seed baseline")
    return rc


if __name__ == "__main__":
    sys.exit(main())
