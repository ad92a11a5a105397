"""CPU rehearsal of the ``panel_sweep`` cell: the harness drives the
segmented sweep at a tiny size with the kernels in interpret mode,
traced and untraced, and the control (the reference in bfloat16 in the
program's place) must come out not correct; the work count stays
within what the program's passes compute.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench

Nothing here describes or loads a TPU; the harness runs with
``require_chip=False``, which never yields a device metric.
"""

from __future__ import annotations

import os
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

CELL = "panel_sweep"
SEED = 2**31 + 23456  # wider than 32 signed bits, as benchmark seeds may be
TINY = {"config": {"n": 4096, "segments": 8, "ref_chunks": 8,
                   "causal_config": {"row_block": 512}}}
CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True, scope="module")
def _cache(tmp_path_factory):
    """A private compile cache for this module, and the process's cache
    settings restored after it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    saved = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax"))
    yield
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def run(*, trace=False, control=False, seconds=0.5):
    from repro.kernels.seg_gram import ops as sg_ops
    with sg_ops.force_backend("interpret"):
        return harness.run_cell(CELL, SEED, seconds, trace, require_chip=False,
                                overrides=TINY, control=control)


@pytest.mark.parametrize("trace", [False, True])
def test_panel_sweep_rehearsal(trace):
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    r = run(trace=trace)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    wanted = {m["name"] for m in harness.metrics_for(bench, CELL, trace)
              if m["source"] == "host_clock"}
    assert wanted and set(r["metrics"]) == wanted
    if trace:
        assert r["metrics"]["compiles_per_sweep.sweep"]["value"] == 0
        assert r["metrics"]["sweep_host_s.sweep"]["value"] > 0
    assert set(r["compared"]) >= {"beta_y_rel", "beta_t_rel", "theta_gap_se",
                                  "se_rel", "empty_cells", "seg_gram_fallbacks"}


def test_panel_sweep_control_fails():
    r = run(control=True)
    assert r["correct"], r["compared"]
    over = {k: v for k, v in r["control"].items()
            if v > r["compared"][k]["limit"]}
    assert over, (r["control"], r["compared"])


def test_sweep_count_within_program_passes():
    """The least work per sweep is below what the program's passes
    compute (every MM step's three 0/1 products and its gradient) and
    reads X at least once a step."""
    spec = harness.cell_spec(CELL)
    config = harness._merge(spec["config"], TINY["config"])
    work = harness.load_module(harness.HERE / "counts" / "dml_sweep.py").work(
        config, spec["traffic"])
    n, p, E = config["n"], config["p"], config["segments"]
    k, q = config["causal_config"]["n_folds"], config["p"] + 1
    steps = 2 * config["causal_config"]["newton_iters"]
    per_step = 2.0 * n * (k * q * q + E * k * q + k * q * k + E * k * q)
    fold_gram = 2.0 * n * (E * k) * (q + 1) ** 2
    assert 0 < work["flops"] <= steps * per_step + fold_gram
    assert work["bytes"] >= steps * 4 * n * p
