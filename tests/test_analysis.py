"""kptlint tests (ISSUE 7): per-rule fixtures, suppression, baseline
round-trip, the package-wide self-clean gate, and the mutation gates the
acceptance criteria name (deleting the PR 6 ``_nested_partition``
layout-mode pin, re-introducing an un-pulled ``np.asarray`` in dist/).

Everything here is pure-AST — no jax import, no device work — so this file
adds milliseconds to tier-1.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from kaminpar_tpu.analysis import ALL_RULES, Analyzer, default_config
from kaminpar_tpu.analysis.baseline import Baseline
from kaminpar_tpu.analysis.core import summarize

REPO = Path(__file__).resolve().parent.parent


def analyze(source: str, rel: str = "kaminpar_tpu/dist/_snippet.py"):
    """Findings (non-suppressed) of a snippet placed at ``rel``."""
    analyzer = Analyzer(ALL_RULES, default_config())
    return [
        f for f in analyzer.check_source(textwrap.dedent(source), rel=rel)
        if not f.suppressed
    ]


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# sync-discipline
# ---------------------------------------------------------------------------


def test_sync_rule_fires_on_unpulled_asarray_in_dist():
    findings = analyze(
        """
        import numpy as np

        def leak(graph):
            return np.asarray(graph.node_w)
        """
    )
    assert "sync-discipline" in rules_of(findings)


def test_sync_rule_fires_on_device_get_and_item_and_coercion():
    findings = analyze(
        """
        import jax
        import jax.numpy as jnp

        def leak(x):
            a = jax.device_get(x)
            b = jnp.sum(x).item()
            c = int(jnp.max(x))
            return a, b, c
        """
    )
    assert sum(f.rule == "sync-discipline" for f in findings) == 3


def test_sync_rule_clean_on_pull_and_host_data():
    findings = analyze(
        """
        import numpy as np
        from ..utils import sync_stats

        def fine(graph, budgets: np.ndarray):
            host = sync_stats.pull(graph.node_w, phase="dist_metrics")
            caps = np.asarray(budgets, dtype=np.int64)
            meta = graph.node_w.dtype
            hist = np.asarray([1, 2, 3])
            return host.sum() + caps.sum(), meta, hist
        """
    )
    assert findings == []


def test_sync_rule_tracks_host_assignments():
    findings = analyze(
        """
        import numpy as np
        from ..utils import sync_stats

        def fine(graph):
            lab = sync_stats.pull(graph.partition)
            again = np.asarray(lab)  # host already: no finding
            return again
        """
    )
    assert findings == []


def test_sync_rule_ignores_io_boundary_modules():
    findings = analyze(
        """
        import numpy as np

        def boundary(graph):
            return np.asarray(graph.node_w)
        """,
        rel="kaminpar_tpu/io/_snippet.py",
    )
    assert "sync-discipline" not in rules_of(findings)


def test_sync_rule_suppression_honored():
    findings = analyze(
        """
        import numpy as np

        def fine(graph):
            return np.asarray(graph.node_w)  # kpt: ignore[sync-discipline]
        """
    )
    assert findings == []


# ---------------------------------------------------------------------------
# runtime-isolation
# ---------------------------------------------------------------------------


def test_runtime_rule_fires_without_layout_pin_and_accepts_pin():
    bad = analyze(
        """
        from ..graph.csr import from_numpy_csr

        def build(sub, ctx):
            g = from_numpy_csr(sub.row_ptr, sub.col_idx, sub.node_w, sub.edge_w)
            return g
        """,
        rel="kaminpar_tpu/partitioning/_snippet.py",
    )
    assert "runtime-isolation" in rules_of(bad)
    good = analyze(
        """
        from ..graph.csr import from_numpy_csr

        def build(sub, ctx):
            g = from_numpy_csr(sub.row_ptr, sub.col_idx, sub.node_w, sub.edge_w)
            g._layout_mode = ctx.parallel.device_layout_build
            return g
        """,
        rel="kaminpar_tpu/partitioning/_snippet.py",
    )
    assert "runtime-isolation" not in rules_of(good)


def test_runtime_rule_bans_process_default_mutators():
    findings = analyze(
        """
        from ..graph.csr import set_layout_build_mode
        from ..utils.timer import set_sync_mode

        def misconfigure(ctx):
            set_layout_build_mode("device")
            set_sync_mode(True)
        """,
        rel="kaminpar_tpu/serve/_snippet.py",
    )
    assert sum(f.rule == "runtime-isolation" for f in findings) == 2


def test_runtime_rule_bans_direct_cache_config():
    findings = analyze(
        """
        import jax

        def sneaky():
            jax.config.update("jax_compilation_cache_dir", "/tmp/x")
        """,
        rel="kaminpar_tpu/ops/_snippet.py",
    )
    assert "runtime-isolation" in rules_of(findings)


def test_mutation_gate_deleting_pr6_layout_pin_fails_lint():
    """Acceptance: deleting the PR 6 _nested_partition layout-mode pin must
    make the lint gate fail.  Run the analyzer over the REAL deep.py source
    and over a mutated copy with the pin line removed."""
    deep_src = (REPO / "kaminpar_tpu/partitioning/deep.py").read_text()
    pin = "g._layout_mode = sub_ctx.parallel.device_layout_build"
    assert pin in deep_src, "the PR 6 pin disappeared from deep.py"

    analyzer = Analyzer(ALL_RULES, default_config())
    rel = "kaminpar_tpu/partitioning/deep.py"
    clean = [
        f for f in analyzer.check_source(deep_src, rel=rel,
                                         modname="kaminpar_tpu.partitioning.deep")
        if not f.suppressed and f.rule == "runtime-isolation"
    ]
    assert clean == [], [f.render() for f in clean]

    mutated = "\n".join(
        line for line in deep_src.splitlines() if pin not in line
    )
    broken = [
        f for f in analyzer.check_source(mutated, rel=rel,
                                         modname="kaminpar_tpu.partitioning.deep")
        if not f.suppressed and f.rule == "runtime-isolation"
    ]
    assert broken, "deleting the layout pin must trip runtime-isolation"
    assert any("'g'" in f.message for f in broken)


def test_mutation_gate_unpulled_asarray_in_dist_fails_lint():
    """Acceptance: re-introducing an un-pulled np.asarray in dist/ must make
    the lint gate fail — mutate the real dist/metrics.py back to the
    pre-fix spelling."""
    src = (REPO / "kaminpar_tpu/dist/metrics.py").read_text()
    fixed = ("return sync_stats.pull(bw, phase=\"dist_metrics\", "
             "shards=graph.num_shards)")
    assert fixed in src
    analyzer = Analyzer(ALL_RULES, default_config())
    rel = "kaminpar_tpu/dist/metrics.py"
    clean = [
        f for f in analyzer.check_source(src, rel=rel,
                                         modname="kaminpar_tpu.dist.metrics")
        if not f.suppressed and f.rule == "sync-discipline"
    ]
    assert clean == [], [f.render() for f in clean]
    mutated = src.replace(fixed, "return np.asarray(bw)")
    broken = [
        f for f in analyzer.check_source(mutated, rel=rel,
                                         modname="kaminpar_tpu.dist.metrics")
        if not f.suppressed and f.rule == "sync-discipline"
    ]
    assert broken, "an un-pulled np.asarray in dist/ must trip sync-discipline"


def test_runtime_rule_accepts_attribute_and_annotated_targets():
    """Review fix: `self.g = CSRGraph(...)` / `g: CSRGraph = ...` with a
    matching pin must not be misreported as an un-pinnable inline
    construction."""
    good = analyze(
        """
        from ..graph.csr import CSRGraph, from_numpy_csr

        class Holder:
            def build(self, s, ctx):
                self.g = CSRGraph(s.a, s.b)
                self.g._layout_mode = ctx.parallel.device_layout_build
                h: CSRGraph = from_numpy_csr(s.a, s.b, s.c, s.d)
                h._layout_mode = ctx.parallel.device_layout_build
                return h
        """,
        rel="kaminpar_tpu/serve/_snippet.py",
    )
    assert "runtime-isolation" not in rules_of(good)
    bad = analyze(
        """
        from ..graph.csr import CSRGraph

        class Holder:
            def build(self, s):
                self.g = CSRGraph(s.a, s.b)
        """,
        rel="kaminpar_tpu/serve/_snippet.py",
    )
    msgs = [f.message for f in bad if f.rule == "runtime-isolation"]
    assert len(msgs) == 1 and "'self.g'" in msgs[0]


def test_sync_rule_sees_through_container_annotations():
    """Review fix: `Sequence[CSRGraph]` must not launder device fields
    through the host-container annotation, while `Sequence[float]` stays
    host."""
    findings = analyze(
        """
        import numpy as np
        from typing import Sequence
        from ..graph.csr import CSRGraph

        def leak(graphs: Sequence[CSRGraph]):
            return [np.asarray(g.node_w) for g in graphs]

        def fine(values: Sequence[float]):
            return np.asarray(values)
        """
    )
    sync = [f for f in findings if f.rule == "sync-discipline"]
    assert len(sync) == 1 and sync[0].line == 7


def test_sync_rule_sees_into_lambda_bodies():
    """Review fix: a materialization inside a lambda must not escape the
    scope-based scan."""
    findings = analyze(
        """
        import numpy as np
        import jax.numpy as jnp

        def leak(vals):
            x = jnp.asarray(vals)
            f = lambda: np.asarray(x)
            return f
        """
    )
    assert "sync-discipline" in rules_of(findings)


def test_ignore_file_past_header_does_not_suppress_line():
    """Review fix: an ignore-file directive after line 10 is inert — it
    neither grants a file-wide exemption nor silently suppresses every rule
    on its own line."""
    src = (
        "import numpy as np\n" + "\n" * 10 +
        "def leak(graph):\n"
        "    return np.asarray(graph.node_w)  # kpt: ignore-file[sync-discipline]\n"
    )
    analyzer = Analyzer(ALL_RULES, default_config())
    findings = [f for f in analyzer.check_source(src) if not f.suppressed]
    assert "sync-discipline" in rules_of(findings)


def test_importmap_resolves_relative_imports_in_package_init():
    """Review fix: level-1 relative imports inside an __init__.py resolve
    against the package itself, not its parent."""
    from kaminpar_tpu.analysis.core import SourceModule

    mod = SourceModule.load(
        REPO / "kaminpar_tpu/serve/__init__.py",
        "kaminpar_tpu/serve/__init__.py",
        "kaminpar_tpu.serve",
    )
    assert (
        mod.imports.names.get("pack_graphs")
        == "kaminpar_tpu.serve.batching.pack_graphs"
    )


# ---------------------------------------------------------------------------
# phase-registry
# ---------------------------------------------------------------------------


def test_phase_rule_fires_on_unregistered_literal():
    findings = analyze(
        """
        from ..utils.timer import scoped_timer

        def work():
            with scoped_timer("coarsning"):  # typo
                pass
        """
    )
    assert "phase-registry" in rules_of(findings)


def test_phase_rule_checks_pull_phase_kwarg():
    findings = analyze(
        """
        from ..utils import sync_stats

        def work(x):
            return sync_stats.pull(x, phase="not_a_phase")
        """
    )
    assert "phase-registry" in rules_of(findings)


def test_phase_rule_accepts_registered_names():
    findings = analyze(
        """
        from ..utils.timer import scoped_timer
        from ..utils import sync_stats

        def work(x):
            with scoped_timer("coarsening"):
                return sync_stats.pull(x, phase="dist_metrics")
        """
    )
    assert "phase-registry" not in rules_of(findings)


def test_phase_rule_reverse_direction_flags_stale_registry():
    """finalize(): a registered phase never referenced anywhere is flagged
    on the registry module."""
    from kaminpar_tpu.analysis.core import SourceModule
    from kaminpar_tpu.analysis.rules import PhaseRegistryRule

    registry = SourceModule.from_source(
        "KNOWN_PHASES = ()\n",
        rel="kaminpar_tpu/telemetry/phases.py",
        modname="kaminpar_tpu.telemetry.phases",
    )
    user = SourceModule.from_source(
        'from ..utils.timer import scoped_timer\n'
        'def f():\n'
        '    with scoped_timer("coarsening"):\n'
        '        pass\n',
        rel="kaminpar_tpu/dist/_snippet.py",
        modname="kaminpar_tpu.dist._snippet",
    )
    rule = PhaseRegistryRule()
    stale = rule.finalize([registry, user], default_config())
    # every KNOWN_PHASES entry except "untracked" and the ones the snippet
    # uses shows up as stale against this tiny module set
    from kaminpar_tpu.telemetry.phases import KNOWN_PHASES

    expect = len(KNOWN_PHASES) - 2  # "untracked" + "coarsening"
    assert len(stale) == expect


# ---------------------------------------------------------------------------
# rng-discipline
# ---------------------------------------------------------------------------


def test_rng_rule_fires_on_np_random_and_stdlib_random():
    findings = analyze(
        """
        import random
        import numpy as np

        def draw():
            rng = np.random.default_rng(0)
            return rng.integers(10) + random.random()
        """
    )
    assert sum(f.rule == "rng-discipline" for f in findings) == 2


def test_rng_rule_fires_on_raw_key_construction():
    findings = analyze(
        """
        import jax

        def key():
            return jax.random.key(0)
        """
    )
    assert "rng-discipline" in rules_of(findings)


def test_rng_rule_accepts_facade():
    findings = analyze(
        """
        from ..utils import RandomState, rng

        def draw():
            host = RandomState.numpy_rng()
            return rng.seed_key(0), rng.lane_key(1, 3), host.integers(10)
        """
    )
    assert "rng-discipline" not in rules_of(findings)


def test_rng_rule_exempts_io_and_generators():
    findings = analyze(
        """
        import numpy as np

        def gen(seed):
            return np.random.default_rng(seed)
        """,
        rel="kaminpar_tpu/graph/generators.py",
    )
    assert "rng-discipline" not in rules_of(findings)


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

_DONATING_DEF = """
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, x):
        return state + x
"""


def test_donation_rule_fires_on_use_after_donate():
    findings = analyze(
        _DONATING_DEF + """
    def caller(state, x):
        out = step(state, x)
        return out + state.sum()
        """
    )
    assert "donation-safety" in rules_of(findings)


def test_donation_rule_accepts_rebinding_idiom():
    findings = analyze(
        _DONATING_DEF + """
    def caller(state, x):
        for _ in range(3):
            state = step(state, x)
        return state
        """
    )
    assert "donation-safety" not in rules_of(findings)


def test_donation_rule_revives_after_rebind():
    findings = analyze(
        _DONATING_DEF + """
    def caller(state, x, fresh):
        out = step(state, x)
        state = fresh
        return out + state.sum()
        """
    )
    assert "donation-safety" not in rules_of(findings)


# ---------------------------------------------------------------------------
# suppressions / baseline machinery
# ---------------------------------------------------------------------------


def test_file_wide_suppression():
    findings = analyze(
        """
        # kpt: ignore-file[sync-discipline]
        import numpy as np

        def leak(graph):
            return np.asarray(graph.node_w)
        """
    )
    assert "sync-discipline" not in rules_of(findings)


def test_baseline_round_trip(tmp_path):
    """run -> baseline-update -> rerun shows zero fresh; removing the
    violation makes the entry stale; an unrelated edit above the site does
    NOT invalidate the entry (line-independent fingerprints)."""
    src = textwrap.dedent(
        """
        import numpy as np

        def leak(graph):
            return np.asarray(graph.node_w)
        """
    )
    analyzer = Analyzer(ALL_RULES, default_config())
    first = [f for f in analyzer.check_source(src) if not f.suppressed]
    assert first
    bl = Baseline.from_findings(first, notes="test")
    path = tmp_path / "baseline.json"
    bl.save(path)
    loaded = Baseline.load(path)
    assert len(loaded) == len(first)

    # same source: everything baselined, nothing fresh
    again = analyzer.check_source(src)
    for f in again:
        if not f.suppressed and loaded.contains(f):
            f.baselined = True
    assert analyzer.fresh(again) == []

    # unrelated edit above the site: fingerprints survive
    shifted = src.replace(
        "import numpy as np", "import numpy as np\nUNRELATED = 1"
    )
    moved = analyzer.check_source(shifted)
    live = [f for f in moved if not f.suppressed]
    assert all(loaded.contains(f) for f in live)

    # fixing the violation leaves a stale entry
    fixed = src.replace("np.asarray(graph.node_w)", "graph.node_w")
    clean = analyzer.check_source(fixed)
    assert loaded.stale_entries(clean) == loaded.entries


def test_summarize_shape():
    src = "import numpy as np\ndef f(g):\n    return np.asarray(g.node_w)\n"
    analyzer = Analyzer(ALL_RULES, default_config())
    findings = analyzer.check_source(src)
    s = summarize(findings)
    assert set(s) == {"fresh", "suppressed", "baselined", "per_rule"}
    assert s["fresh"] == s["per_rule"].get("sync-discipline", 0) > 0


# ---------------------------------------------------------------------------
# package-wide gates
# ---------------------------------------------------------------------------


def test_package_self_clean():
    """The whole package carries zero non-baselined violations — the tier-1
    lint gate (same analysis `tools lint` runs)."""
    config = default_config()
    baseline = Baseline.load(REPO / "kptlint_baseline.json")
    analyzer = Analyzer(ALL_RULES, config)
    findings = analyzer.run(baseline=baseline)
    fresh = analyzer.fresh(findings)
    assert fresh == [], "fresh kptlint violations:\n" + "\n".join(
        f.render() for f in fresh
    )


def test_lint_cli_json_and_exit_code():
    out = subprocess.run(
        [sys.executable, "-m", "kaminpar_tpu.tools", "lint", "--json"],
        capture_output=True, text=True, timeout=120,
        cwd=str(REPO),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["summary"]["fresh"] == 0
    assert "baseline_size" in payload["summary"]


def test_lint_cli_list_rules():
    out = subprocess.run(
        [sys.executable, "-m", "kaminpar_tpu.tools", "lint", "--list-rules"],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
    )
    assert out.returncode == 0
    for rule in ("sync-discipline", "runtime-isolation", "phase-registry",
                 "rng-discipline", "donation-safety"):
        assert rule in out.stdout


# ---------------------------------------------------------------------------
# error-discipline (round 17, ISSUE 13)
# ---------------------------------------------------------------------------


def test_error_rule_fires_on_bare_runtimeerror():
    findings = analyze(
        """
        def f(x):
            raise RuntimeError("device pool failed")
        """,
        rel="kaminpar_tpu/serve/_snippet.py",
    )
    assert "error-discipline" in rules_of(findings)


def test_error_rule_fires_on_unclassified_dispatch_handler():
    """The pre-round-17 engine pattern: a broad except around a dispatch
    site wrapping the failure in an untyped ServeError."""
    findings = analyze(
        """
        class ServeError(RuntimeError):
            pass

        def f(solver, reqs):
            try:
                solver.compute_partition(4, 0.03)
            except Exception as exc:
                for r in reqs:
                    r.future._reject(ServeError(f"batch failed: {exc!r}"))
        """,
        rel="kaminpar_tpu/serve/_snippet.py",
    )
    assert "error-discipline" in rules_of(findings)


def test_error_rule_fires_on_laundered_valueerror():
    findings = analyze(
        """
        def f(g):
            try:
                return g.dispatch()
            except Exception as exc:
                raise ValueError(str(exc))
        """,
        rel="kaminpar_tpu/ops/_snippet.py",
    )
    assert "error-discipline" in rules_of(findings)


def test_error_rule_clean_on_classify_and_validation():
    """classify-routed handlers, typed raises, bare re-raises, narrow
    handlers, and plain argument validation all pass."""
    findings = analyze(
        """
        from ..resilience.errors import ExecuteFault, classify

        def f(solver, k):
            if k <= 0:
                raise ValueError("k must be positive")
            try:
                return solver.compute_partition(k, 0.03)
            except KeyError:
                return None
            except Exception as exc:
                raise classify(exc, site="test")

        def g(solver):
            try:
                return solver.compute_partition(2, 0.03)
            except Exception:
                raise ExecuteFault("typed", site="test")

        def h(solver):
            try:
                return solver.compute_partition(2, 0.03)
            except Exception:
                raise
        """,
        rel="kaminpar_tpu/serve/_snippet.py",
    )
    assert "error-discipline" not in rules_of(findings)


def test_error_rule_mutation_gate_engine_loop():
    """Deleting the classify routing from the real engine dispatcher
    handler trips error-discipline on the real source."""
    engine_src = (REPO / "kaminpar_tpu" / "serve" / "engine.py").read_text()
    rel = "kaminpar_tpu/serve/engine.py"
    analyzer = Analyzer(ALL_RULES, default_config())
    clean = [
        f for f in analyzer.check_source(
            engine_src, rel=rel, modname="kaminpar_tpu.serve.engine"
        )
        if not f.suppressed and f.rule == "error-discipline"
    ]
    assert clean == []
    assert "err = classify(exc, site=\"dispatch\")" in engine_src
    mutated = engine_src.replace(
        "err = classify(exc, site=\"dispatch\")",
        "err = ServeError(f\"batch failed: {exc!r}\")",
    ).replace(
        "from ..resilience.errors import classify\n\n                err",
        "err",
    )
    fired = [
        f for f in analyzer.check_source(
            mutated, rel=rel, modname="kaminpar_tpu.serve.engine"
        )
        if not f.suppressed and f.rule == "error-discipline"
    ]
    assert fired, "mutated dispatcher handler must trip error-discipline"


def test_every_shipped_rule_has_fire_and_suppress_coverage():
    """Meta-gate: each shipped rule fires on at least one fixture above AND
    honors suppression (spot-checked here for the remaining rules)."""
    fixtures = {
        "sync-discipline": "import numpy as np\ndef f(g):\n"
                           "    return np.asarray(g.node_w)\n",
        "runtime-isolation": "from ..graph.csr import from_numpy_csr\n"
                             "def f(s):\n"
                             "    g = from_numpy_csr(s.a, s.b, s.c, s.d)\n"
                             "    return g\n",
        "phase-registry": "from ..utils.timer import scoped_timer\n"
                          "def f():\n"
                          "    with scoped_timer(\"zz_bogus\"):\n"
                          "        pass\n",
        "rng-discipline": "import random\n",
        "donation-safety": (
            "from functools import partial\nimport jax\n"
            "@partial(jax.jit, donate_argnums=(0,))\n"
            "def step(s):\n    return s\n"
            "def f(s):\n    out = step(s)\n    return out, s\n"
        ),
        "error-discipline": (
            "def f(solver):\n"
            "    try:\n"
            "        return solver.compute_partition(2, 0.03)\n"
            "    except Exception as exc:\n"
            "        raise RuntimeError(str(exc))\n"
        ),
    }
    analyzer = Analyzer(ALL_RULES, default_config())
    for rule, src in fixtures.items():
        fired = analyzer.check_source(src)
        assert any(
            f.rule == rule and not f.suppressed for f in fired
        ), f"{rule} fixture did not fire"
        lines = src.splitlines()
        suppressed_src = "\n".join(
            [f"# kpt: ignore-file[{rule}]"] + lines
        ) + "\n"
        silent = analyzer.check_source(suppressed_src)
        assert not any(
            f.rule == rule and not f.suppressed for f in silent
        ), f"{rule} suppression not honored"
