"""The shipped kptlint rule set.

Each rule targets an invariant the codebase already asserts dynamically —
the static pass extends coverage from executed paths to the whole package
(see the package docstring of :mod:`kaminpar_tpu.analysis`):

==================  =======================================================
sync-discipline     host-materialization primitives in device-disciplined
                    modules must route through ``sync_stats.pull``
runtime-isolation   pipeline code reaches cache/layout/sync settings
                    through the active ``EngineRuntime``, never the
                    process defaults (the PR 6 escape class)
phase-registry      phase string literals <-> telemetry/phases.KNOWN_PHASES
                    in both directions
rng-discipline      randomness flows from utils/rng (lane keys or the
                    RandomState facade), never np.random / stdlib random
donation-safety     buffers donated via donate_argnums are not referenced
                    after the jitted call
error-discipline    pipeline/serve failures route through the round-17
                    resilience hierarchy (no bare RuntimeError, dispatch-
                    site handlers call resilience.errors.classify)
==================  =======================================================
"""

from .donation_safety import DonationSafetyRule
from .error_discipline import ErrorDisciplineRule
from .phase_registry import PhaseRegistryRule
from .rng_discipline import RngDisciplineRule
from .runtime_isolation import RuntimeIsolationRule
from .sync_discipline import SyncDisciplineRule

ALL_RULES = (
    SyncDisciplineRule(),
    RuntimeIsolationRule(),
    PhaseRegistryRule(),
    RngDisciplineRule(),
    DonationSafetyRule(),
    ErrorDisciplineRule(),
)

RULES_BY_NAME = {r.name: r for r in ALL_RULES}

__all__ = [
    "ALL_RULES",
    "RULES_BY_NAME",
    "SyncDisciplineRule",
    "RuntimeIsolationRule",
    "PhaseRegistryRule",
    "RngDisciplineRule",
    "DonationSafetyRule",
    "ErrorDisciplineRule",
]
