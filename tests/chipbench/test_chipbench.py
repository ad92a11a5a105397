"""CPU tests of the chip benchmark: every cell at tiny sizes with the
kernel in interpret mode, the control and the planted faults, the trace
reduction, the work counts, and a cell added as new files only.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench

Nothing here describes or loads a TPU; the harness is driven with
``require_chip=False``, which never yields a device metric.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import faults, harness, trace_reduce  # noqa: E402

SEED = 2**31 + 12345  # wider than 32 signed bits, as the driver's are

TINY_FIT = {"config": {"n": 4096, "p": 20, "ref_chunks": 8,
                       "causal_config": {"n_bootstrap": 4, "row_block": 512}}}
CELLS = {"fig6_fit_ci": TINY_FIT}


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True, scope="module")
def _cache(tmp_path_factory):
    """A private compile cache for this module (nothing from another
    machine is loaded), and the process's cache settings restored after
    it, so that later modules in the same worker see none of it."""
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


def run(cell, *, trace=False, control=False, seconds=0.5, overrides=None):
    from repro.kernels.seg_gram import ops as sg_ops
    with sg_ops.force_backend("interpret"):
        return harness.run_cell(cell, SEED, seconds, trace, require_chip=False,
                                overrides=CELLS[cell] if overrides is None
                                else overrides,
                                control=control)


def device_metrics(bench, trace):
    group = bench["per_layer" if trace else "end_to_end"]
    return {m["name"] for m in group if m["source"] == "device_trace"}


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearsal(cell, trace):
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    r = run(cell, trace=trace)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert not set(r["metrics"]) & device_metrics(bench, trace)
    assert "breakdown" not in r and "busy_s" not in r["device"]
    wanted = {m["name"] for m in harness.metrics_for(bench, cell, trace)
              if m["source"] == "host_clock"}
    assert set(r["metrics"]) == wanted
    assert list(r)[-1] == "compared"


def test_cli_refuses_without_chip(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    p = subprocess.run([sys.executable, str(ROOT / "chipbench" / "run.py"),
                        "--workload", "fig6_fit_ci", "--seed", str(SEED),
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 3 and p.stdout == ""
    assert "needs 1 TPU" in p.stderr


def test_cli_refuses_without_program(tmp_path):
    """A checkout of the benchmark's own files alone has nothing to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "fig6_fit_ci", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout == ""


# ---------------------------------------------------------------------------
# The control (the reference in bfloat16 in the program's place) and the
# planted faults must each come out not correct
# ---------------------------------------------------------------------------

CONTROL_SIZES = {"config": {"n": 16384, "p": 50, "ref_chunks": 8,
                            "causal_config": {"n_bootstrap": 4, "row_block": 2048}}}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails(cell):
    r = run(cell, control=True, overrides=CONTROL_SIZES)
    assert r["correct"], r["compared"]
    over = {k: v for k, v in r["control"].items()
            if v > r["compared"][k]["limit"]}
    assert over, (r["control"], r["compared"])


FAULTS = [("fig6_fit_ci", f) for f in sorted(faults.FAULTS)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    faults.FAULTS[fault](monkeypatch.setattr)
    r = run(cell)
    assert not r["correct"], r["compared"]


# ---------------------------------------------------------------------------
# Trace reduction on a small recorded trace
# ---------------------------------------------------------------------------

TRACE = pathlib.Path(__file__).with_name("tiny_trace.pbtxt")


def test_trace_reduce():
    from jax.profiler import ProfileData
    tr = trace_reduce.from_profile(ProfileData.from_text_proto(TRACE.read_text()))
    r = trace_reduce.reduce(tr)
    # window 1 .. 11 ms; ops clipped to it: kernel 1-3, fusion 2-4,
    # fusion 6-7, kernel 8.5-11 -> busy 3 + 1 + 2.5 ms, kernels 2 + 2.5 ms;
    # gaps 4-6 (inside the refresh mark) and 7-8.5 (no mark)
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(6.5e-3)
    assert r["kernel_s"] == pytest.approx(4.5e-3)
    assert r["idle_gaps"] == [["refresh", pytest.approx(2e-3)],
                              ["harness", pytest.approx(1.5e-3)]]
    assert r["top_ops"][0] == ["jit_other:%k.2 custom-call", pytest.approx(2.5e-3)]
    assert r["top_ops"][-1] == ["%g fusion", pytest.approx(1e-3)]  # in no module


def test_trace_reduce_without_device():
    assert trace_reduce.reduce({"devices": {}, "marks": []}) is None


# ---------------------------------------------------------------------------
# Work counts: never above what the program's passes compute, and at least
# one read of the data the unit needs
# ---------------------------------------------------------------------------

def _passes(monkeypatch):
    """Record (n, S, qL, qR) of every kernel dispatch at trace time."""
    import jax

    from repro.kernels.seg_gram import kernel
    seen = []
    orig = kernel.seg_gram_pallas

    def rec(builder, arrays, *, seg=None, w=None, n_segments=1, **kw):
        shapes = [jax.ShapeDtypeStruct((8,) + a.shape[1:] if a.shape[0] != 1
                                       else a.shape, a.dtype) for a in arrays]
        L, R = jax.eval_shape(builder, *shapes)
        n = max(a.shape[0] for a in arrays)
        seen.append((n, n_segments, L.shape[1], R.shape[1]))
        return orig(builder, arrays, seg=seg, w=w, n_segments=n_segments, **kw)

    monkeypatch.setattr(kernel, "seg_gram_pallas", rec)
    return seen


POINT_FITS = {**TINY_FIT, "traffic": {"bootstrap": False}}


def test_point_fit_count_within_program_passes(monkeypatch):
    seen = _passes(monkeypatch)
    r = run("fig6_fit_ci", overrides=POINT_FITS)
    assert r["correct"] and "replicate_gap_se" not in r["compared"]
    spec = harness.cell_spec("fig6_fit_ci")
    config = harness._merge(spec["config"], TINY_FIT["config"])
    work = harness.load_module(harness.HERE / "counts" / "dml_fit.py").work(
        config, POINT_FITS["traffic"])
    implemented = sum(2.0 * n * ql * qr for n, s, ql, qr in seen)
    assert seen and 0 < work["flops"] <= implemented
    assert work["bytes"] >= 4 * config["n"] * config["p"]


@pytest.mark.parametrize("bootstrap", [True, False])
def test_fit_count_within_program_passes(bootstrap):
    spec = harness.cell_spec("fig6_fit_ci")
    config = harness._merge(spec["config"], TINY_FIT["config"])
    work = harness.load_module(harness.HERE / "counts" / "dml_fit.py").work(
        config, {**spec["traffic"], "bootstrap": bootstrap})
    n, p, k = config["n"], config["p"], config["causal_config"]["n_folds"]
    fits = 1 + (config["causal_config"]["n_bootstrap"] if bootstrap else 0)
    q = p + 2  # the program: [X | 1 | target], k fold Grams per nuisance
    implemented = fits * 2 * 2.0 * n * (k * q) * q
    assert 0 < work["flops"] <= implemented
    assert work["bytes"] >= fits * 4 * n * p


# ---------------------------------------------------------------------------
# A configuration, a cell and a per-layer metric added as new files only
# ---------------------------------------------------------------------------

def test_new_cell_from_new_files_only(tmp_path, monkeypatch):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.read_json(ROOT / "BENCHMARK.json")
    here = tmp_path / "chipbench"
    conf = harness.read_json(here / "configs" / "dml_fig6_n1m_p500.json")
    conf = harness._merge(conf, TINY_FIT["config"])
    conf.update(name="tiny_fit", n=2048)
    (here / "configs" / "tiny_fit.json").write_text(json.dumps(conf))
    (here / "traffic" / "tiny_fits.json").write_text(json.dumps(
        {"driver": "fit_ci", "count": "dml_fit", "bootstrap": False,
         "check_replicates": 0}))
    limits = harness.read_json(here / "limits" / "fig6_fit_ci.json")
    del limits["replicate_gap_se"]  # point fits: no replicates to compare
    (here / "limits" / "tiny_cell.json").write_text(json.dumps(limits))
    (here / "layers" / "fit_share.tiny.py").write_text(
        "from chipbench.readers import window_to_last_unit\n\n\n"
        "def read(run):\n"
        "    return 100.0 * sum(run.span_seconds('point_fit')) / "
        "window_to_last_unit(run)\n")
    bench["configs"].append({"name": "tiny_fit", "source": "a test",
                             "file": "chipbench/configs/tiny_fit.json",
                             "reduced": ["n"], "why": "a test"})
    bench["workloads"].append({"name": "tiny_cell", "config": "tiny_fit",
                               "traffic": "tiny_fits", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "fit_rows_per_s":
            m["workloads"].append("tiny_cell")
    bench["per_layer"].append({"name": "fit_share.tiny", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "estimator", "moves": "fit_rows_per_s",
                               "workloads": ["tiny_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "HERE", here)
    for trace in (False, True):
        r = run("tiny_cell", trace=trace, overrides={})
        assert r["correct"]
        want = {"fit_share.tiny"} if trace else {"fit_rows_per_s", "setup_s"}
        assert set(r["metrics"]) == want
