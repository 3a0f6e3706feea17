"""CI perf-regression gate: diff two ``BENCH_*.json`` rounds.

The bench harness (bench.py) leaves one ``BENCH_rNN.json`` per round
with a ``parsed`` dict of per-section numbers.  This script compares
the HEADLINE columns of two rounds — the numbers the README/ROADMAP
make claims about — and **fails (exit 1) on any regression beyond the
tolerance**, so a perf claim can't silently rot between rounds:

- ``adam.speedup_vs_eager`` / ``adam.speedup_vs_jitted_optax``
  (fused-Adam engine speedups),
- every ``*.mfu_vs_measured_roofline`` (GPT MFU),
- every ``*.tokens_per_sec`` (training + serving throughput),
- every ``*.cross_slice_wire_cut`` (hierarchical sync's headline),
- every ``*.wire_cut_vs_default`` (compressed sync's headline),
- ``gpt124_s4096.mfu_ratio_vs_s1024`` (long-context MFU retention).

All headline columns are higher-is-better; tolerance is relative
(``--max-regression-pct``, default 10 — bench noise on a shared
machine is real).  Columns present in only one round are REPORTED as
skipped, never failed: a round that lost a section (preflight wedge,
``--only`` run) must not turn the gate red, and a round that gained
one has no baseline yet.

Usage::

    python benchmarks/bench_compare.py                 # two newest rounds
    python benchmarks/bench_compare.py OLD.json NEW.json
    python benchmarks/bench_compare.py --max-regression-pct 5
    python benchmarks/bench_compare.py --columns 'adam.*' ...  # extra paths

Exit codes: 0 ok / nothing comparable, 1 regression(s), 2 usage or
unreadable input.
"""

import argparse
import fnmatch
import glob
import json
import os
import re
import sys

#: terminal path components that ARE headline columns (all
#: higher-is-better; a lower-is-better column would need a direction
#: table — add it here when one becomes a headline)
HEADLINE_LEAVES = (
    "speedup_vs_eager",
    "speedup_vs_jitted_optax",
    "mfu_vs_measured_roofline",
    "tokens_per_sec",
    "cross_slice_wire_cut",
    "cross_dcn_wire_cut",
    "wire_cut_vs_default",
    "mfu_ratio_vs_s1024",
)


def flatten(tree, prefix=""):
    """Dotted-path -> numeric leaf over the ``parsed`` dict (numbers
    only — strings/lists/None are metadata, not metrics)."""
    out = {}
    if not isinstance(tree, dict):
        return out
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[path] = float(v)
    return out


def load_round(path):
    """The flattened metrics of one BENCH_*.json (its ``parsed`` dict,
    falling back to the top level for hand-crafted fixtures)."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a JSON object")
    return flatten(doc.get("parsed", doc))


def newest_pair(root):
    """The two newest ``BENCH_r*.json`` under ``root``, (old, new) by
    round number (the rNN suffix — mtimes lie after a git checkout)."""

    def round_no(p):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        return (int(m.group(1)) if m else -1, p)

    rounds = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")),
                    key=round_no)
    if len(rounds) < 2:
        return None
    return rounds[-2], rounds[-1]


def is_headline(path, extra_globs=()):
    leaf = path.rsplit(".", 1)[-1]
    return leaf in HEADLINE_LEAVES or any(
        fnmatch.fnmatch(path, g) for g in extra_globs)


def compare(old_metrics, new_metrics, max_regression_pct=10.0,
            extra_globs=()):
    """``{"regressions": [...], "improvements": [...], "ok": [...],
    "skipped": [...]}`` over the headline columns of two flattened
    rounds.  A regression is ``new < old * (1 - pct/100)`` on a
    higher-is-better column present in BOTH."""
    result = {"regressions": [], "improvements": [], "ok": [],
              "skipped": []}
    paths = sorted(set(old_metrics) | set(new_metrics))
    for path in paths:
        if not is_headline(path, extra_globs):
            continue
        old, new = old_metrics.get(path), new_metrics.get(path)
        if old is None or new is None:
            result["skipped"].append(
                {"column": path,
                 "missing_in": "old" if old is None else "new"})
            continue
        if old <= 0:
            result["skipped"].append(
                {"column": path, "missing_in": "old_nonpositive"})
            continue
        change_pct = 100.0 * (new - old) / old
        rec = {"column": path, "old": old, "new": new,
               "change_pct": round(change_pct, 2)}
        if change_pct < -max_regression_pct:
            result["regressions"].append(rec)
        elif change_pct > max_regression_pct:
            result["improvements"].append(rec)
        else:
            result["ok"].append(rec)
    return result


def main(argv=None):
    p = argparse.ArgumentParser(
        description="fail on >X%% regressions between two BENCH rounds' "
                    "headline columns")
    p.add_argument("files", nargs="*",
                   help="OLD.json NEW.json (default: the two newest "
                        "BENCH_r*.json in the repo root)")
    p.add_argument("--max-regression-pct", type=float, default=10.0,
                   help="relative drop that fails the gate (default 10)")
    p.add_argument("--columns", action="append", default=[],
                   help="extra dotted-path globs to treat as headline "
                        "(repeatable, e.g. 'zero_gpt124.*.ms_per_step')")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report on stdout")
    args = p.parse_args(argv)

    if len(args.files) == 2:
        old_path, new_path = args.files
    elif not args.files:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pair = newest_pair(root)
        if pair is None:
            print("bench_compare: fewer than two BENCH_r*.json rounds — "
                  "nothing to gate", file=sys.stderr)
            return 0
        old_path, new_path = pair
    else:
        p.error("pass exactly two files, or none for the newest pair")

    try:
        old_metrics = load_round(old_path)
        new_metrics = load_round(new_path)
    except (OSError, ValueError) as e:
        print(f"bench_compare: {e}", file=sys.stderr)
        return 2

    result = compare(old_metrics, new_metrics,
                     max_regression_pct=args.max_regression_pct,
                     extra_globs=args.columns)
    result["old_file"] = old_path
    result["new_file"] = new_path

    if args.json:
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        print(f"bench_compare: {os.path.basename(old_path)} -> "
              f"{os.path.basename(new_path)} "
              f"(tolerance {args.max_regression_pct:g}%)")
        for rec in result["regressions"]:
            print(f"  REGRESSION {rec['column']}: {rec['old']:g} -> "
                  f"{rec['new']:g} ({rec['change_pct']:+.1f}%)")
        for rec in result["improvements"]:
            print(f"  improved   {rec['column']}: {rec['old']:g} -> "
                  f"{rec['new']:g} ({rec['change_pct']:+.1f}%)")
        for rec in result["ok"]:
            print(f"  ok         {rec['column']}: {rec['old']:g} -> "
                  f"{rec['new']:g} ({rec['change_pct']:+.1f}%)")
        for rec in result["skipped"]:
            print(f"  skipped    {rec['column']} "
                  f"(missing in {rec['missing_in']})")
    return 1 if result["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
