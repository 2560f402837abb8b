"""TOML config round-trip tests (reference: the CLI11 --dump-config/-C
machinery used by apps/KaMinPar.cc)."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Subprocesses run on the CPU backend and import this checkout.
_ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO}

from kaminpar_tpu.config import dump_toml, load_toml
from kaminpar_tpu.context import RefinementAlgorithm
from kaminpar_tpu.presets import create_context_by_preset_name, get_preset_names


def test_dump_load_roundtrip_all_presets():
    for name in get_preset_names():
        ctx = create_context_by_preset_name(name)
        text = dump_toml(ctx)
        ctx2 = load_toml(text)
        assert ctx2.to_dict() == ctx.to_dict(), name


def test_load_overrides():
    ctx = load_toml(
        """
preset_name = "fast"
seed = 7

[coarsening.lp]
num_iterations = 3

[refinement]
algorithms = ["jet"]
"""
    )
    assert ctx.preset_name == "fast"
    assert ctx.seed == 7
    assert ctx.coarsening.lp.num_iterations == 3
    assert ctx.refinement.algorithms == (RefinementAlgorithm.JET,)


def test_load_rejects_unknown_key():
    import pytest

    with pytest.raises(ValueError, match="unknown config key"):
        load_toml("[coarsening]\nnot_a_field = 1\n")


def test_cli_dump_config():
    out = subprocess.run(
        [sys.executable, "-m", "kaminpar_tpu", "-P", "eco", "--dump-config"],
        capture_output=True, text=True, timeout=120, env=_ENV,
    )
    assert out.returncode == 0, out.stderr
    assert 'preset_name = "eco"' in out.stdout
    assert "[refinement]" in out.stdout


def test_assertion_ladder():
    """KASSERT ladder (reference: kaminpar-common/assert.h:40-50): checks
    above the active level are skipped; callables defer evaluation."""
    import pytest

    from kaminpar_tpu.utils.assertions import (
        HEAVY,
        LIGHT,
        assertion_level,
        kassert,
        set_assertion_level,
    )

    prev = assertion_level()
    try:
        set_assertion_level("always")
        kassert(False, "inactive at always", LIGHT)  # no raise
        exploded = []
        kassert(lambda: exploded.append(1) or True, "", HEAVY)
        assert not exploded  # heavy callable never evaluated
        set_assertion_level("heavy")
        with pytest.raises(AssertionError, match="boom"):
            kassert(lambda: False, "boom", HEAVY)
    finally:
        set_assertion_level(
            {1: "always", 2: "light", 3: "normal", 4: "heavy", 0: "none"}[prev]
        )


def test_dist_preset_ladder():
    """dist preset ladder (reference: dist presets.cc:18-286)."""
    from kaminpar_tpu.context import (
        DistClusteringAlgorithm,
        RefinementAlgorithm,
    )
    from kaminpar_tpu.presets import create_context_by_preset_name

    fast = create_context_by_preset_name("dist-fast")
    assert fast.coarsening.dist_clustering == DistClusteringAlgorithm.LOCAL_GLOBAL_LP
    strong = create_context_by_preset_name("dist-strong")
    assert RefinementAlgorithm.CLP in strong.refinement.algorithms
    assert RefinementAlgorithm.JET in strong.refinement.algorithms
    largek = create_context_by_preset_name("dist-largek")
    assert largek.initial_partitioning.device_extension


def test_engine_runtime_ownership_no_first_wins():
    """ISSUE 6 unlocking refactor: the first-wins configure_* records are
    gone — each facade/engine owns an :class:`EngineRuntime` and activates
    it thread-locally, so two conflicting configs coexist in one process
    with no RuntimeWarning and *independent* behavior inside each
    activation."""
    import warnings

    from kaminpar_tpu import context as ctx_mod
    from kaminpar_tpu.context import EngineRuntime, ParallelContext
    from kaminpar_tpu.graph.csr import resolve_layout_build_mode
    from kaminpar_tpu.utils import timer

    prev_mode = timer.sync_mode()
    try:
        rt_a = EngineRuntime.from_parallel(
            ParallelContext(sync_timers=False, device_layout_build="host")
        )
        rt_b = EngineRuntime.from_parallel(
            ParallelContext(sync_timers=True, device_layout_build="device")
        )
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # conflicting configs: no warning
            with rt_a.activate():
                assert timer.sync_mode() is False
                assert resolve_layout_build_mode() == "host"
                # Nested activation (engine dispatch inside a facade run):
                # the inner runtime wins, the outer is restored after.
                with rt_b.activate():
                    assert timer.sync_mode() is True
                    assert resolve_layout_build_mode() == "device"
                assert timer.sync_mode() is False
                assert resolve_layout_build_mode() == "host"
        assert ctx_mod.current_runtime() is None
    finally:
        timer.set_sync_mode(prev_mode)


def _cache_dir_in_subprocess(env_dir, code=""):
    """``jax_compilation_cache_dir`` as a fresh interpreter sees it after
    ``import kaminpar_tpu`` and ``code``; ``env_dir`` None runs it without
    JAX_COMPILATION_CACHE_DIR."""
    env = dict(_ENV)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    script = (
        "import jax, kaminpar_tpu\n"
        + code
        + "\nprint('CACHE_DIR=' + str(jax.config.jax_compilation_cache_dir))"
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=300, env=env, cwd=_REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1].split("=", 1)[1]


def test_engine_runtime_cache_isolation(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory:
    importing the package keeps it, and a facade run under its own
    EngineRuntime activation leaves it in place."""
    env_dir = str(tmp_path / "env_cache")
    run = (
        "from kaminpar_tpu.graph import generators\n"
        "s = kaminpar_tpu.KaMinPar('fast')\n"
        "s.set_graph(generators.grid2d_graph(8, 8))\n"
        "s.compute_partition(2)"
    )
    assert _cache_dir_in_subprocess(env_dir) == env_dir
    assert _cache_dir_in_subprocess(env_dir, run) == env_dir


def test_engine_runtime_restores_process_default_cache():
    """Without JAX_COMPILATION_CACHE_DIR the cache lives at
    <checkout>/.xla_cache, and activating runtimes (nested, or on two
    threads at once) never moves it."""
    import threading

    import jax

    from kaminpar_tpu import CHECKOUT_DIR
    from kaminpar_tpu.context import EngineRuntime, ParallelContext

    assert _cache_dir_in_subprocess(None) == os.path.join(
        CHECKOUT_DIR, ".xla_cache"
    )
    before = jax.config.jax_compilation_cache_dir
    rt_a = EngineRuntime.from_parallel(ParallelContext())
    rt_b = EngineRuntime.from_parallel(
        ParallelContext(device_layout_build="host")
    )
    with rt_a.activate():
        with rt_b.activate():
            assert jax.config.jax_compilation_cache_dir == before

    a_in, b_in, a_out = (threading.Event() for _ in range(3))

    def thread_a():
        with rt_a.activate():
            a_in.set()
            b_in.wait(10)
        a_out.set()

    def thread_b():
        a_in.wait(10)
        with rt_b.activate():  # enters while A is still active
            b_in.set()
            a_out.wait(10)  # exits after A

    ta = threading.Thread(target=thread_a)
    tb = threading.Thread(target=thread_b)
    ta.start(); tb.start(); ta.join(15); tb.join(15)
    assert not ta.is_alive() and not tb.is_alive()
    assert jax.config.jax_compilation_cache_dir == before


def test_serve_context_roundtrips_and_preset():
    from kaminpar_tpu.config import dump_toml as _dump, load_toml as _load
    from kaminpar_tpu.presets import create_context_by_preset_name

    ctx = create_context_by_preset_name("serve")
    ctx.serve.warm_ladder = (64, 128)
    ctx.serve.default_deadline_ms = 250.0
    ctx2 = _load(_dump(ctx))
    assert ctx2.serve.warm_ladder == (64, 128)
    assert ctx2.serve.default_deadline_ms == 250.0
    assert ctx2.serve.max_batch == ctx.serve.max_batch
