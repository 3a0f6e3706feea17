"""CLI: ``python -m apex_tpu.analysis [paths] [options]``.

Exit codes: 0 clean (modulo baseline), 1 findings, 2 usage/baseline
error.  With no paths, scans the repo's default surface (``apex_tpu``,
``examples`` — whichever exist under the current directory) against
``analysis_baseline.json`` when present.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from apex_tpu.analysis import (
    BaselineError, analyze_paths, apply_baseline, default_rules,
    discover_axis_registry, load_baseline, sarif, write_baseline,
)

DEFAULT_PATHS = ("apex_tpu", "examples")
DEFAULT_BASELINE = "analysis_baseline.json"


def _find_default_baseline(paths):
    """The committed baseline lives at the repo root; the CLI may be
    invoked from anywhere (pre-commit hooks, CI jobs with their own
    CWD).  Search the CWD, then each scanned root and its parents, so
    absolute-path invocations still pick the suppressions up instead of
    silently reporting baselined findings as live."""
    candidates = [os.getcwd()]
    for p in paths:
        d = os.path.abspath(p) if os.path.isdir(p) \
            else os.path.dirname(os.path.abspath(p))
        while True:
            candidates.append(d)
            parent = os.path.dirname(d)
            if parent == d:
                break
            d = parent
    for c in candidates:
        f = os.path.join(c, DEFAULT_BASELINE)
        if os.path.isfile(f):
            return f
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.analysis",
        description=__doc__.split("\n\n")[0])
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to scan (default: "
                         f"{' '.join(DEFAULT_PATHS)} where present)")
    ap.add_argument("--baseline", default=None,
                    help=f"suppression file (default: the first "
                         f"{DEFAULT_BASELINE} found in the CWD or above "
                         f"any scanned path)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline: report everything")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline file for the CURRENT "
                         "findings: kept entries verbatim, stale "
                         "entries dropped, new findings added with a "
                         "justification of 'TODO' that the loader "
                         "REJECTS — the refresh is mechanical, the "
                         "review is not skippable")
    ap.add_argument("--check-baseline", action="store_true",
                    help="additionally FAIL (exit 1) when any baseline "
                         "entry no longer suppresses a finding — stale "
                         "suppressions rot silently otherwise (an entry "
                         "whose code was fixed keeps matching the next "
                         "unrelated finding that drifts into its "
                         "substring)")
    ap.add_argument("--format", choices=("text", "json", "sarif"),
                    default="text")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="parallelize the per-file parse/index pass "
                         "across N worker processes (module linking and "
                         "rule checks stay single-pass; results are "
                         "identical to --jobs 1)")
    ap.add_argument("--only-rules", default=None, metavar="IDS",
                    help="comma-separated rule ids to run (e.g. "
                         "APX209,APX210) — everything else is skipped; "
                         "unknown ids are a usage error, not a silent "
                         "no-op scan")
    ap.add_argument("--skip-rules", default=None, metavar="IDS",
                    help="comma-separated rule ids to skip; combines "
                         "with --only-rules (skip wins)")
    ap.add_argument("--timing", action="store_true",
                    help="print per-rule wall time (plus the shared "
                         "<load>/<link> phases) to stderr, slowest "
                         "first, then a per-family rollup (trace/io "
                         "APX1xx, concurrency APX114-116, distributed "
                         "APX2xx, kernel APX3xx, numerics APX4xx)")
    ap.add_argument("--timing-json", default=None, metavar="FILE",
                    help="also write the raw timings dict (rule id -> "
                         "seconds, plus <load>/<link>) as JSON to FILE "
                         "— the CI artifact next to the SARIF "
                         "(implies --timing collection)")
    ap.add_argument("--axes", default=None,
                    help="comma-separated collective-axis registry "
                         "override (default: *_AXIS constants parsed "
                         "from any scanned parallel_state.py)")
    ap.add_argument("--vmem-budget-mib", type=float, default=None,
                    help="APX304 per-pallas_call VMEM budget in MiB "
                         "(default 16)")
    args = ap.parse_args(argv)
    if args.update_baseline and args.no_baseline:
        # --no-baseline loads nothing, so the rewrite would drop every
        # reviewed justification and emit TODOs for the whole tree
        ap.error("--update-baseline with --no-baseline would discard "
                 "every existing justification; drop one of the flags")

    paths = args.paths or [p for p in DEFAULT_PATHS if os.path.exists(p)]
    if not paths:
        ap.error("no paths given and none of the defaults exist here")
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        ap.error(f"no such path: {missing}")

    registry = (set(a for a in args.axes.split(",") if a)
                if args.axes is not None else discover_axis_registry(paths))
    rules = default_rules(
        vmem_budget_bytes=None if args.vmem_budget_mib is None
        else int(args.vmem_budget_mib * 2 ** 20))
    known = {r.rule_id for r in rules}

    def _rule_ids(flag, value):
        ids = [x.strip() for x in value.split(",") if x.strip()]
        unknown = sorted(set(ids) - known)
        if unknown:
            ap.error(f"{flag}: unknown rule id(s) {unknown} — "
                     f"available: {', '.join(sorted(known))}")
        return set(ids)

    if args.only_rules is not None:
        rules = tuple(r for r in rules
                      if r.rule_id in _rule_ids("--only-rules",
                                                args.only_rules))
    if args.skip_rules is not None:
        rules = tuple(r for r in rules
                      if r.rule_id not in _rule_ids("--skip-rules",
                                                    args.skip_rules))
    if not rules:
        ap.error("--only-rules/--skip-rules left nothing to run")
    timings = {} if (args.timing or args.timing_json) else None
    findings = analyze_paths(paths, rules, registry, jobs=args.jobs,
                             timings=timings)
    if args.timing and timings is not None:
        for name, secs in sorted(timings.items(),
                                 key=lambda kv: -kv[1]):
            print(f"timing: {name:10s} {secs:8.3f}s", file=sys.stderr)
        families = {"APX1": "trace/io", "APX2": "distributed",
                    "APX3": "kernel", "APX4": "numerics"}
        concurrency = {"APX114", "APX115", "APX116"}
        rollup: dict = {}
        for name, secs in timings.items():
            if name in concurrency:
                fam = "concurrency"
            else:
                fam = families.get(name[:4],
                                   "shared" if name.startswith("<")
                                   else "other")
            rollup[fam] = rollup.get(fam, 0.0) + secs
        for fam, secs in sorted(rollup.items(), key=lambda kv: -kv[1]):
            print(f"timing: family {fam:12s} {secs:8.3f}s",
                  file=sys.stderr)
    if args.timing_json and timings is not None:
        with open(args.timing_json, "w") as fh:
            json.dump(dict(sorted(timings.items())), fh, indent=2)
            fh.write("\n")

    entries = []
    baseline_path = args.baseline or _find_default_baseline(paths)
    if not args.no_baseline:
        bootstrapping = (args.update_baseline and baseline_path
                         and not os.path.isfile(baseline_path))
        if baseline_path and not bootstrapping:
            try:
                entries = load_baseline(
                    baseline_path, allow_todo=args.update_baseline)
            except BaselineError as e:
                print(f"error: {e}", file=sys.stderr)
                return 2
    kept, suppressed, stale = apply_baseline(findings, entries)

    if args.update_baseline:
        target = baseline_path or DEFAULT_BASELINE
        n_kept, n_dropped, n_added = write_baseline(
            target, findings, entries)
        print(f"{target}: kept {n_kept} entr(ies), dropped {n_dropped} "
              f"stale, added {n_added} with justification \"TODO\""
              + (" — fill every TODO in before the next run will load "
                 "this file" if n_added else ""),
              file=sys.stderr)
        return 0

    if args.format == "sarif":
        print(json.dumps(sarif.render(kept, suppressed, rules), indent=2))
        if kept:
            # the red-CI-log summary: the SARIF document is for the
            # editor/code-scanning upload, not for the human reading
            # the failed job — name the damage on stderr too
            by_rule: dict = {}
            for f in kept:
                by_rule.setdefault(f.rule, 0)
                by_rule[f.rule] += 1
            rules_s = ", ".join(f"{r} x{n}" if n > 1 else r
                                for r, n in sorted(by_rule.items()))
            print(f"{len(kept)} finding(s) [{rules_s}], "
                  f"{len(suppressed)} baselined, {len(stale)} stale "
                  f"baseline entr(ies) — full detail in the SARIF "
                  f"document above", file=sys.stderr)
    elif args.format == "json":
        print(json.dumps({
            "findings": [f.to_json() for f in kept],
            "suppressed": [f.to_json() for f in suppressed],
            "stale_baseline_entries": [
                {"rule": e.rule, "path": e.path, "symbol": e.symbol}
                for e in stale],
            "axes": sorted(registry),
        }, indent=2))
    else:
        for f in kept:
            print(f.render())
        for e in stale:
            print(f"note: stale baseline entry ({e.rule} {e.path} "
                  f"{e.symbol}) suppresses nothing — remove it",
                  file=sys.stderr)
        print(f"{len(kept)} finding(s), {len(suppressed)} baselined, "
              f"{len(stale)} stale baseline entr(ies)", file=sys.stderr)
    if args.check_baseline and stale:
        for e in stale:
            print(f"error: stale baseline entry ({e.rule} {e.path} "
                  f"{e.symbol}) suppresses nothing — the code it "
                  f"covered was fixed; remove the entry "
                  f"(--check-baseline)", file=sys.stderr)
        return 1
    return 1 if kept else 0


if __name__ == "__main__":
    sys.exit(main())
