"""CPU rehearsal of the ``panel_daily`` cell: the harness drives a
windowed moment store (3 days of 1,024 rows, one more day retiring the
oldest in set-up, days back to back in the window) at a tiny size with
the kernels in interpret mode, traced and untraced; the control (the
reference in bfloat16 in the program's place) must come out not
correct; the work count stays within what the program's passes compute.

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

CELL = "panel_daily"
SEED = 2**31 + 65432  # wider than 32 signed bits, as benchmark seeds may be
TINY = {"config": {"n": 3 * 1024, "days": 3, "day_rows": 1024, "segments": 4,
                   "ref_chunks": 3, "causal_config": {"row_block": 512}},
        "traffic": {"pool_days": 2}}
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
def test_panel_daily_rehearsal(trace):
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    r = run(trace=trace)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    wanted = {m["name"] for m in harness.metrics_for(bench, CELL, trace)
              if m["source"] == "host_clock"}
    assert wanted and set(r["metrics"]) == wanted
    if trace:
        assert r["metrics"]["compiles_per_day.daily"]["value"] == 0
        assert r["metrics"]["store_ingest_s.daily"]["value"] > 0
        assert r["metrics"]["store_refresh_s.daily"]["value"] > 0
    else:  # a day counts the window's rows
        assert r["metrics"]["fit_rows_per_s"]["value"] > 0
    assert set(r["compared"]) >= {"beta_y_rel", "beta_t_rel", "theta_gap_se",
                                  "se_rel", "empty_cells", "seg_gram_fallbacks"}


def test_panel_daily_control_fails():
    r = run(control=True)
    assert r["correct"], r["compared"]
    over = {k: v for k, v in r["control"].items()
            if v > r["compared"][k]["limit"]}
    assert over, (r["control"], r["compared"])


def test_daily_pool_cycles_with_new_days():
    """Past the pool the days cycle through it: day d reads the pool's
    day 3 + (d - 3) % 2, while its global rows (and folds) are new."""
    from chipbench.drivers import panel_daily
    ctx = type("Ctx", (), {})()
    ctx.config = {"days": 3}
    ctx.state = {"days": list("abcde")}
    assert [panel_daily._host_day(ctx, d) for d in range(9)] == [
        "a", "b", "c", "d", "e", "d", "e", "d", "e"]


def test_daily_count_within_program_passes():
    """The least work per day is below what the program's passes
    compute (the day's fold Gram, every MM step's 0/1 products and its
    gradient over the window) and reads the window's X once a step."""
    spec = harness.cell_spec(CELL)
    config = harness._merge(spec["config"], TINY["config"])
    work = harness.load_module(harness.HERE / "counts" / "store_daily.py").work(
        config, spec["traffic"])
    R, p, E = config["day_rows"], config["p"], config["segments"]
    N = config["days"] * R
    k, q = config["causal_config"]["n_folds"], p + 1
    steps = 2 * config["causal_config"]["newton_iters"]
    per_step = 2.0 * N * (k * q * q + E * k * q + k * q * k + E * k * q)
    day_gram = 2.0 * R * (E * k) * (q + 2) ** 2
    assert 0 < work["flops"] <= steps * per_step + day_gram
    assert work["bytes"] >= steps * 4 * N * p
