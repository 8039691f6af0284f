"""The readers of the program's spans (``bench/layers``: ``fit_host_ms``,
``mean_var_host_ms``, ``mean_var_programs_per_call``,
``device_idle_in_mean_var_pct``): known answers on a hand-built trace, and
every reader finding its spans in a trace recorded on a TPU v5 lite chip
by ``bench/record_trace.py`` (one ``GP.fit`` and one ``GP.mean_var`` at
p = 2, n = 8)."""
from pathlib import Path

import pytest

from bench.lib import spec, trace
from bench.lib.common import LayerData

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
SPANS = TESTDATA / "spans_trace.xplane.pb"
READERS = ("fit_host_ms", "mean_var_host_ms", "mean_var_programs_per_call",
           "device_idle_in_mean_var_pct")
PROGRAM_SPANS = ("gp.fit", "fagp.fit.prepare", "fagp.system", "fagp.factor",
                 "gp.mean_var", "fagp.features", "fagp.posterior")


def _run(tr):
    return LayerData(cell=None, peak=None, trace=tr, counters={})


def _read(name, tr):
    return spec.load_reader(name)(_run(tr))


def _trace(host_spans=True):
    ev = trace.Event
    ops = {0: [ev("fusion.1", 150, 180), ev("fusion.2", 500, 700)],
           1: [ev("fusion.1", 0, 1000)]}
    mods = {0: [ev("jit_integer_pow(1)", 120, 130), ev("jit_a(2)", 150, 180),
                ev("jit_b(3)", 500, 700), ev("jit_c(4)", 800, 810),
                ev("jit_d(5)", 990, 1010)],          # crosses the window's end
            1: [ev("jit_a(2)", 0, 1000)]}            # not the first device
    host = [ev("bench.window", 0, 1000), ev("harvest", 700, 800)]
    if host_spans:
        host += [ev("gp.fit", 10, 90),
                 ev("gp.mean_var", 100, 200), ev("gp.mean_var", 400, 600),
                 ev("fagp.features", 420, 450),
                 ev("gp.mean_var", 950, 1050)]      # crosses the window's end
    return trace.Trace(t0_ns=0, t1_ns=1000, ops=ops, modules=mods, host=host)


def test_host_ms_is_the_mean_span_inside_the_window():
    tr = _trace()
    assert _read("mean_var_host_ms", tr) == pytest.approx(150e-6)
    assert _read("fit_host_ms", tr) == pytest.approx(80e-6)


def test_programs_per_call_counts_the_first_device_in_the_window():
    # four programs of device 0 lie inside [0, 1000]; two calls do
    assert _read("mean_var_programs_per_call", _trace()) == pytest.approx(2.0)


def test_idle_in_mean_var_is_span_time_without_device_ops():
    tr = _trace()
    # device 0: idle 70 of the first span, 100 of the second; device 1: 0
    value = _read("device_idle_in_mean_var_pct", tr)
    assert value == pytest.approx(100.0 * (170 + 0) / 2 / 1000)
    assert value <= trace.idle_pct(tr)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_spans(name):
    assert _read(name, _trace(host_spans=False)) is None
    assert spec.load_reader(name)(
        LayerData(cell=None, peak=None, trace=None, counters={})) is None


def test_recorded_chip_trace_has_every_span():
    tr = trace.reduce(str(SPANS))
    names = {e.name for e in trace.in_window(tr, tr.host)}
    assert set(PROGRAM_SPANS) <= names
    assert not [n for n in names if n.split("#")[0] in PROGRAM_SPANS
                and "#" in n]


@pytest.mark.parametrize("name", READERS)
def test_recorded_chip_trace_feeds_every_reader(name):
    tr = trace.reduce(str(SPANS))
    value = _read(name, tr)
    assert value is not None and value > 0
    if name == "mean_var_programs_per_call":
        assert value >= 2
    if name == "device_idle_in_mean_var_pct":
        assert value <= trace.idle_pct(tr)
