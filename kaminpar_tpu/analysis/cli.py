"""``tools lint`` — the kptlint command-line entry point.

Text output for humans, ``--json`` for machines (bench.py embeds the same
summary shape in its artifact), ``--baseline-update`` to (re)grandfather
the current fresh findings, nonzero exit on fresh violations.  Pure-AST:
never imports jax, so it never waits on a device backend and runs in
milliseconds as part of tier-1.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from .baseline import DEFAULT_BASELINE_NAME, Baseline
from .core import Analyzer, default_config, summarize
from .rules import ALL_RULES

_BASELINE_NOTES = (
    "kptlint grandfather file. Entries are fingerprinted by (rule, path, "
    "normalized source line, occurrence index) — line numbers are "
    "informational. Regenerate with: python -m kaminpar_tpu.tools lint "
    "--baseline-update. Policy: new code never adds entries; fix the "
    "violation or justify an inline '# kpt: ignore[rule]' instead."
)


def run_lint(argv) -> int:
    p = argparse.ArgumentParser(
        prog="lint",
        description="kptlint: static device-discipline checks "
        "(sync budget, runtime isolation, phase registry, RNG, donation)",
    )
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable findings + summary on stdout")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help=f"baseline path (default: <repo>/{DEFAULT_BASELINE_NAME})")
    p.add_argument("--baseline-update", action="store_true",
                   help="rewrite the baseline from the current fresh "
                        "findings and exit 0")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding as fresh (audit mode)")
    p.add_argument("--rules", default=None, metavar="R1,R2",
                   help="run only these rules")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--show-baselined", action="store_true",
                   help="also print baselined findings (text mode)")
    args = p.parse_args(argv)

    if args.list_rules:
        for rule in ALL_RULES:
            print(f"{rule.name:<20} {rule.description}")
        return 0

    config = default_config()
    if args.rules:
        config.enabled_rules = tuple(
            r.strip() for r in args.rules.split(",") if r.strip()
        )
    baseline_path = Path(
        args.baseline
        if args.baseline
        else config.repo_root / DEFAULT_BASELINE_NAME
    )
    baseline = None
    if not args.no_baseline and not args.baseline_update:
        baseline = Baseline.load(baseline_path)

    analyzer = Analyzer(ALL_RULES, config)
    findings = analyzer.run(baseline=baseline)
    fresh = analyzer.fresh(findings)

    if args.baseline_update:
        notes = _BASELINE_NOTES
        if baseline_path.is_file():
            notes = Baseline.load(baseline_path).notes or notes
        Baseline.from_findings(fresh, notes=notes).save(baseline_path)
        print(f"baseline updated: {len(fresh)} entries -> {baseline_path}")
        return 0

    summary = summarize(findings)
    summary["baseline_size"] = len(baseline) if baseline is not None else 0
    if baseline is not None:
        summary["baseline_stale"] = len(baseline.stale_entries(findings))

    if args.as_json:
        print(json.dumps({
            "findings": [
                f.to_dict() for f in findings
                if not f.suppressed and (args.show_baselined or not f.baselined)
            ],
            "summary": summary,
        }, indent=2))
    else:
        for f in findings:
            if f.suppressed or (f.baselined and not args.show_baselined):
                continue
            tag = " [baselined]" if f.baselined else ""
            print(f.render() + tag)
            if f.snippet:
                print(f"    {f.snippet}")
        print(
            f"kptlint: {summary['fresh']} fresh, "
            f"{summary['baselined']} baselined, "
            f"{summary['suppressed']} suppressed "
            f"({', '.join(f'{k}={v}' for k, v in summary['per_rule'].items()) or 'clean'})"
        )
        if summary.get("baseline_stale"):
            print(
                f"kptlint: {summary['baseline_stale']} baseline entries are "
                "stale (fixed violations) — run --baseline-update to prune"
            )
    return 1 if fresh else 0


def lint_summary() -> dict:
    """The summary dict alone (bench.py embeds this in its JSON artifact so
    violation drift shows up in the perf trajectory)."""
    config = default_config()
    baseline = Baseline.load(config.repo_root / DEFAULT_BASELINE_NAME)
    analyzer = Analyzer(ALL_RULES, config)
    findings = analyzer.run(baseline=baseline)
    summary = summarize(findings)
    summary["baseline_size"] = len(baseline)
    return summary
