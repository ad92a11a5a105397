"""CPU tests of the per-layer metrics that read the program's own spans
(``chipbench/program_spans.py``, ``layers/compiles_per_fit.fit.py``,
``compile_s_per_fit.fit.py``, ``plan_s_per_fit.fit.py``): what each
reads from the process tracer, the window they keep to, and that a
program without a process tracer gives nothing to read.

    JAX_PLATFORMS=cpu python -m pytest -q tests/chipbench
"""

from __future__ import annotations

import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from repro.obs import trace  # noqa: E402

READERS = ("compiles_per_fit.fit", "compile_s_per_fit.fit",
           "plan_s_per_fit.fit")
S = 10**9  # ns per second


def reader(name):
    return harness.load_module(harness.HERE / "layers" / f"{name}.py").read


def window(units=2, start=100.0, end=200.0):
    return types.SimpleNamespace(units=[{}] * units, window_start=start,
                                 window_end=end)


@pytest.fixture
def tracer():
    trace.reset_process_tracer()
    yield trace.process_tracer()
    trace.reset_process_tracer()


def test_readers_divide_window_spans_by_fits(tracer):
    add = tracer.add_span
    add("compile.backend", 50 * S, 60 * S)  # before the window: not read
    add("compile.backend", 110 * S, 113 * S)
    add("compile.cache_load", 111 * S, 112 * S)  # inside the backend span
    add("compile.trace", 120 * S, 121 * S)
    add("compile.backend", 150 * S, 151 * S)
    add("runtime.plan", 130 * S, 134 * S)
    add("runtime.plan", 160 * S, 161 * S)
    add("runtime.plan", 250 * S, 260 * S)  # after the window: not read
    run = window(units=2)
    assert reader("compiles_per_fit.fit")(run) == 1.0
    assert reader("compile_s_per_fit.fit")(run) == pytest.approx(2.5)
    assert reader("plan_s_per_fit.fit")(run) == pytest.approx(2.5)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_zero_without_spans_and_none_without_units(tracer, name):
    assert reader(name)(window(units=3)) == 0
    assert reader(name)(window(units=0)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_none_from_a_program_without_process_tracer(
        monkeypatch, name):
    monkeypatch.delattr(trace, "process_tracer")
    assert reader(name)(window()) is None
