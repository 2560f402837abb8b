"""kptlint — AST-level enforcement of the device-discipline contracts.

The runtime tripwires (:mod:`utils.sync_stats`'s implicit-sync patcher, the
phase-registry warn, the transfer-guard armer) only cover *executed* paths;
PR 6 proved the gap: nested-extension thread-pool workers silently bypassed
the ``EngineRuntime`` isolation contract because thread-local activation is
invisible in pool workers — a bug class no test executed until review.
This package makes the contracts *statically checkable* over the whole
package on every tier-1 run:

- :mod:`core` — the rule framework: source loading, import-alias
  resolution, inline ``# kpt: ignore[rule]`` suppressions, per-rule
  configuration, and the analyzer driver.
- :mod:`hostness` — a small per-function host/device value classifier the
  sync rule uses to tell a genuine device->host materialization from host
  numpy bookkeeping.
- :mod:`baseline` — fingerprinted grandfathering of pre-existing findings
  (line-number independent, so unrelated edits don't invalidate entries).
- :mod:`rules` — the shipped rule set (sync-discipline, runtime-isolation,
  phase-registry, rng-discipline, donation-safety).
- :mod:`cli` — the ``python -m kaminpar_tpu.tools lint`` entry point (text
  + JSON output, ``--baseline-update``, nonzero exit on fresh violations).

Everything here is pure-stdlib AST work: the analyzer never imports jax, so
the lint gate runs in milliseconds and never waits on a device backend.
"""

from .core import Analyzer, Finding, LintConfig, default_config
from .rules import ALL_RULES

__all__ = ["Analyzer", "Finding", "LintConfig", "default_config", "ALL_RULES"]
