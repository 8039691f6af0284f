"""Telemetry battery: metrics registry, span tracer, recompile watchdog,
exporters, and the claims the instrumented serving stack makes about them.

The subsystem's contracts, each pinned here:

* Counters / gauges / histograms follow Prometheus semantics (monotonic
  counters, ``le``-inclusive cumulative buckets), reject type conflicts
  and bucket redefinitions, and survive concurrent recording exactly.
* ``snapshot()`` and ``render_prometheus()`` expose the SAME series —
  every counter/gauge series in the snapshot appears verbatim in the
  text exposition with the same value.
* Collectors hold bound methods weakly: a dead engine's flush callback
  is pruned instead of pinning the engine (and its bank) forever.
* ANY interleaving of nested spans + instants across threads produces
  JSONL that ``tools/check_trace.py`` accepts: schema keys present,
  phases valid, durations non-negative, spans properly nested per
  (pid, tid) track (property-based via tests/hypcompat).
* The recompile watchdog catches a shape-polymorphic call through a
  registered executable (raise mode) and stays SILENT across arbitrary
  submit/observe/ingest churn on a warmed engine.
* The no-op defaults allocate nothing on the record path (tracemalloc).
* ``LatencyStats`` memory is bounded: the reservoir never exceeds its
  bound while true counts keep counting, and stays a uniform sample.
* The checkpoint store counts reaped dead-writer staging dirs and async
  worker failures on the process-default registry.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import subprocess
import sys
import tempfile
import threading
import urllib.request
from pathlib import Path
from random import Random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.bank import BankRouter, FleetEngine, GPBank, LatencyStats
from repro.core.gp import GPSpec
from repro.data import make_gp_dataset
from repro.obs import (
    NULL,
    NULL_TRACER,
    MetricsRegistry,
    NullTracer,
    RecompileError,
    RecompileWatchdog,
    SPAN_SCHEMA_KEYS,
    Tracer,
    serving_watchdog,
    set_default,
    start_metrics_server,
)
from repro.obs.metrics import _NULL_INSTRUMENT

from hypcompat import given, settings, st  # hypothesis, or fixed examples

ROOT = Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location(
    "check_trace", ROOT / "tools" / "check_trace.py")
check_trace_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_trace_mod)


def _fleet(B, N, p, n, *, seed=0):
    spec = GPSpec.create(n, eps=[0.8] * p, rho=2.0, noise=0.05,
                         backend="jnp")
    Xb = np.zeros((B, N, p), np.float32)
    yb = np.zeros((B, N), np.float32)
    for s in range(B):
        X, y, *_ = make_gp_dataset(N, p, seed=seed + s)
        Xb[s], yb[s] = np.asarray(X), np.asarray(y)
    return GPBank.fit(jnp.asarray(Xb), jnp.asarray(yb), spec)


# --------------------------------------------------------------------------
# registry: instrument semantics
# --------------------------------------------------------------------------


class TestInstruments:
    def test_counter_monotone_and_labelled_series(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs_total", "requests", tenant="a")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert c.series == 'reqs_total{tenant="a"}'
        # same (name, labels) -> same instrument; new labels -> new series
        assert reg.counter("reqs_total", tenant="a") is c
        other = reg.counter("reqs_total", tenant="b")
        assert other is not c and other.value == 0

    def test_gauge_set_inc_dec(self):
        g = MetricsRegistry().gauge("depth")
        g.set(7.0)
        g.inc(2.0)
        g.dec()
        assert g.value == 8.0

    def test_histogram_buckets_are_le_inclusive(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 2.0, 3.0, 100.0):
            h.record(v)
        snap = reg.snapshot()["histograms"]["lat"]
        # 2.0 lands in le=2.0 (inclusive), 100.0 only in +Inf
        assert snap["buckets"] == {"1.0": 1, "2.0": 2, "4.0": 3, "+Inf": 4}
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(105.5)

    def test_record_many_matches_loop_of_records(self):
        vals = list(np.random.default_rng(0).exponential(0.01, 200))
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        h1, h2 = r1.histogram("h"), r2.histogram("h")
        for v in vals:
            h1.record(v)
        h2.record_many(vals)
        assert h1.counts == h2.counts
        assert h1.sum == pytest.approx(h2.sum)
        assert h1.count == h2.count

    def test_type_conflict_and_bucket_redefinition_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x_total")
        reg.histogram("h", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="different"):
            reg.histogram("h", buckets=(1.0, 2.0, 3.0))
        with pytest.raises(ValueError, match="sorted"):
            reg.histogram("h2", buckets=(2.0, 1.0))

    def test_concurrent_recording_is_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total")
        h = reg.histogram("work", buckets=(0.5,))

        def pound():
            for _ in range(5000):
                c.inc()
                h.record(0.25)

        threads = [threading.Thread(target=pound) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 20000
        assert h.count == 20000 and h.counts[0] == 20000


# --------------------------------------------------------------------------
# exporters: one schema, two views
# --------------------------------------------------------------------------


def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("served_total", "queries served", tenant="a").inc(3)
    reg.counter("served_total", tenant="b").inc(5)
    reg.gauge("queue_depth").set(11)
    h = reg.histogram("latency_seconds", buckets=(0.01, 0.1))
    for v in (0.005, 0.05, 0.5):
        h.record(v)
    return reg


class TestExporters:
    def test_snapshot_schema(self):
        snap = _populated_registry().snapshot()
        assert set(snap) == {"counters", "gauges", "histograms"}
        assert snap["counters"]['served_total{tenant="a"}'] == 3
        assert snap["gauges"]["queue_depth"] == 11
        json.dumps(snap)                     # JSON-serializable, always

    def test_prometheus_round_trip_matches_snapshot(self):
        reg = _populated_registry()
        snap = reg.snapshot()
        text = reg.render_prometheus()
        values = {}
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            series, val = line.rsplit(" ", 1)
            values[series] = float(val)
        for series, v in snap["counters"].items():
            assert values[series] == v
        for series, v in snap["gauges"].items():
            assert values[series] == v
        # histogram expands to cumulative _bucket + _sum + _count
        assert values['latency_seconds_bucket{le="0.01"}'] == 1
        assert values['latency_seconds_bucket{le="0.1"}'] == 2
        assert values['latency_seconds_bucket{le="+Inf"}'] == 3
        assert values["latency_seconds_count"] == 3
        assert "# TYPE served_total counter" in text
        assert "# TYPE latency_seconds histogram" in text

    def test_http_endpoint_serves_both_formats(self):
        reg = _populated_registry()
        server = start_metrics_server(reg, port=0)
        try:
            with urllib.request.urlopen(server.url, timeout=5) as r:
                body = r.read().decode()
            assert 'served_total{tenant="a"} 3' in body
            with urllib.request.urlopen(
                server.url + ".json", timeout=5
            ) as r:
                snap = json.loads(r.read())
            assert snap == reg.snapshot()
        finally:
            server.shutdown()

    def test_collectors_flush_at_scrape_and_die_with_owner(self):
        reg = MetricsRegistry()

        class Engine:
            def __init__(self):
                self.flushes = 0

            def flush(self):
                self.flushes += 1
                reg.counter("flushes_total").inc()

        eng = Engine()
        reg.add_collector(eng.flush)
        reg.snapshot()
        reg.render_prometheus()
        assert eng.flushes == 2
        del eng
        gc.collect()
        # dead owner: collector pruned silently, scrape unaffected
        snap = reg.snapshot()
        assert snap["counters"]["flushes_total"] == 2
        assert len(reg._collectors) == 0
        # plain closures are held strongly
        hits = []
        reg.add_collector(lambda: hits.append(1))
        gc.collect()
        reg.snapshot()
        assert hits == [1]


# --------------------------------------------------------------------------
# tracer: valid Chrome-trace JSONL under any interleaving
# --------------------------------------------------------------------------


def _emit_random_tree(tracer, rng, depth=0):
    for i in range(rng.randrange(0, 4 - depth)):
        with tracer.span(f"d{depth}_{i}", depth=depth):
            if depth < 3 and rng.random() < 0.7:
                _emit_random_tree(tracer, rng, depth + 1)
            if rng.random() < 0.4:
                tracer.instant("tick", i=i)


class TestTracer:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_random_interleavings_validate(self, seed):
        rng = Random(seed)
        tracer = Tracer()
        worker = threading.Thread(
            target=_emit_random_tree, args=(tracer, Random(seed + 1)))
        worker.start()
        _emit_random_tree(tracer, rng)
        worker.join()
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
        for ev in tracer.events():
            assert all(k in ev for k in SPAN_SCHEMA_KEYS)
            assert ev["ph"] in ("X", "i")
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
        with tempfile.TemporaryDirectory() as td:
            path = Path(td) / "t.jsonl"
            n = tracer.write_jsonl(path)
            assert n == len(tracer)
            errors = check_trace_mod.check_trace(
                path, expect=("outer", "inner"))
            assert errors == []

    def test_buffer_bound_counts_drops(self):
        tracer = Tracer(limit=3)
        for i in range(5):
            tracer.instant(f"e{i}")
        assert len(tracer) == 3 and tracer.dropped == 2
        tracer.clear()
        assert len(tracer) == 0 and tracer.dropped == 0

    def test_to_chrome_envelope(self):
        tracer = Tracer()
        with tracer.span("s", bucket=8):
            pass
        doc = tracer.to_chrome()
        assert doc["traceEvents"][0]["args"] == {"bucket": 8}
        assert doc["displayTimeUnit"] == "ms"

    def test_null_tracer_writes_empty_file(self, tmp_path):
        p = tmp_path / "empty.jsonl"
        assert NullTracer().write_jsonl(p) == 0
        assert p.read_text() == ""


class TestCheckTraceValidator:
    def _errs(self, tmp_path, lines, **kw):
        p = tmp_path / "t.jsonl"
        p.write_text("\n".join(lines) + "\n" if lines else "")
        return check_trace_mod.check_trace(p, **kw)

    def _ev(self, **over):
        ev = {"name": "s", "ph": "X", "ts": 0, "dur": 10, "pid": 1,
              "tid": 1}
        ev.update(over)
        return json.dumps(ev)

    def test_rejects_malformed_lines(self, tmp_path):
        assert any("empty" in e for e in self._errs(tmp_path, []))
        assert any("not JSON" in e
                   for e in self._errs(tmp_path, ["{oops"]))
        assert any("missing keys" in e
                   for e in self._errs(tmp_path, ['{"name": "x"}']))
        assert any("unknown phase" in e
                   for e in self._errs(tmp_path, [self._ev(ph="B")]))
        assert any("bad dur" in e
                   for e in self._errs(tmp_path, [self._ev(dur=-1)]))

    def test_rejects_overlapping_non_nested_spans(self, tmp_path):
        bad = [self._ev(name="a", ts=0, dur=100),
               self._ev(name="b", ts=50, dur=100)]
        assert any("without nesting" in e for e in self._errs(tmp_path, bad))
        ok = [self._ev(name="a", ts=0, dur=100),
              self._ev(name="b", ts=10, dur=20),
              self._ev(name="c", ts=40, dur=20),
              self._ev(name="d", ts=200, dur=5, tid=2)]
        assert self._errs(tmp_path, ok) == []

    def test_expect_flags_missing_stage(self, tmp_path):
        lines = [self._ev(name="dispatch")]
        assert self._errs(tmp_path, lines, expect=("dispatch",)) == []
        assert any("never traced" in e
                   for e in self._errs(tmp_path, lines,
                                       expect=("harvest",)))


# --------------------------------------------------------------------------
# recompile watchdog
# --------------------------------------------------------------------------


class TestWatchdog:
    def test_catches_shape_polymorphic_call(self):
        f = jax.jit(lambda x: x * 2.0)
        f(jnp.zeros(4, jnp.float32))
        wd = RecompileWatchdog(mode="raise").register("f", f)
        wd.arm()
        assert wd.check("steady") == {}
        f(jnp.zeros(8, jnp.float32))        # new shape -> new executable
        with pytest.raises(RecompileError, match=r"f \+1"):
            wd.check("leak")
        assert wd.recompiles == 1 and wd.events[0][0] == "leak"
        # baseline advanced: the same compile is reported once
        assert wd.check("after") == {}

    def test_warn_and_count_modes(self):
        f = jax.jit(lambda x: x + 1.0)
        f(jnp.zeros(2, jnp.float32))
        reg = MetricsRegistry()
        wd = RecompileWatchdog(
            mode="warn", counter=reg.counter("recompiles_total"))
        wd.register("f", f).arm()
        f(jnp.zeros(3, jnp.float32))
        with pytest.warns(RuntimeWarning, match="recompile detected"):
            wd.check("churn")
        assert reg.snapshot()["counters"]["recompiles_total"] == 1
        wd.mode = "count"
        f(jnp.zeros(5, jnp.float32))
        assert wd.check() == {"f": 1}       # silent, still counted
        assert wd.recompiles == 2

    def test_register_rejects_non_jitted(self):
        with pytest.raises(TypeError, match="_cache_size"):
            RecompileWatchdog().register("f", lambda x: x)
        with pytest.raises(ValueError, match="mode"):
            RecompileWatchdog(mode="explode")

    def test_serving_watchdog_covers_the_serving_path(self):
        reg = MetricsRegistry()
        wd = serving_watchdog(mode="count", metrics=reg)
        assert {
            "bank_write_slot", "bank_update_scatter",
            "bank_gathered_posterior", "bank_downdate_scatter",
            "bank_refit_scatter", "hyperopt_lane_step",
        } <= set(wd.sizes())
        # the counter series exists even before any growth
        assert "serve_recompiles_total" in reg.snapshot()["counters"]

    def test_silent_across_engine_churn(self):
        bank = _fleet(4, 8, 2, 4)
        wd = serving_watchdog(mode="count")
        router = BankRouter(bank, microbatch=8, ingest_chunk=4)
        eng = FleetEngine(router, auto_pump=False, max_coalesce=2,
                          watchdog=wd)
        rng = np.random.default_rng(7)
        # warm each dispatch rung plus one ingest round, then arm
        for rung in eng.buckets:
            for _ in range(rung):
                eng.submit(int(rng.integers(0, 4)),
                           rng.uniform(-1, 1, 2).astype(np.float32))
            eng.pump(max_blocks=1)
            eng.drain()
        for t in range(4):
            eng.observe(t, rng.uniform(-1, 1, 2).astype(np.float32),
                        float(rng.normal()))
        eng.ingest()
        wd.arm()
        wd.recompiles, wd.events = 0, []
        wd.mode = "raise"                   # any growth now fails loudly
        for _ in range(6):
            for _ in range(int(rng.integers(1, 17))):
                eng.submit(int(rng.integers(0, 4)),
                           rng.uniform(-1, 1, 2).astype(np.float32))
            for t in range(4):
                eng.observe(t, rng.uniform(-1, 1, 2).astype(np.float32),
                            float(rng.normal()))
            eng.drain()
            eng.ingest()
        wd.check("churn-final")
        assert wd.recompiles == 0 and wd.events == []


    def test_silent_across_tiered_lifecycle_churn(self, tmp_path):
        """A tiered fleet (16 tenants through 8 hot slots, a sliding
        window) runs whole submit / observe / ingest / age rounds through
        the engine; once every shape is warm, the armed rounds mint no
        executable, and each age forgets exactly the rows observed that
        round."""
        from repro.bank import TieredBank

        B, cap, rows, obs_per_tenant = 16, 8, 40, 2
        aged_ids = list(range(cap))          # fixed list -> fixed bucket
        spec = GPSpec.create(6, eps=[0.8, 0.8], rho=2.0, noise=0.1,
                             backend="jnp")
        Xb = np.zeros((B, rows, 2), np.float32)
        yb = np.zeros((B, rows), np.float32)
        for s in range(B):
            X, y, *_ = make_gp_dataset(rows, 2, seed=s)
            Xb[s], yb[s] = np.asarray(X), np.asarray(y)
        # window = the fitted row count, so each age forgets exactly the
        # rows the round observed and the armed rounds repeat the warm
        # rounds' downdate shapes
        tb = TieredBank.fit(jnp.asarray(Xb), jnp.asarray(yb), spec,
                            cold_dir=tmp_path / "cold", capacity=cap,
                            window=rows)
        wd = serving_watchdog(mode="count")
        eng = FleetEngine(BankRouter(tb.bank, microbatch=8),
                          max_in_flight=2, tiered=tb, auto_pump=False,
                          watchdog=wd)
        rng = np.random.default_rng(5)

        def churn_round():
            tks = [eng.submit(int(rng.integers(0, B)),
                              rng.uniform(-1, 1, 2).astype(np.float32))
                   for _ in range(64)]
            out = eng.drain()
            assert all(out[t].ok for t in tks)
            for t in range(B):
                for _ in range(obs_per_tenant):
                    eng.observe(t, rng.uniform(-1, 1, 2).astype(np.float32),
                                float(rng.normal()))
            eng.ingest()
            tb.adopt(eng.router.bank)
            aged = tb.age(aged_ids)
            eng.router.bank = tb.bank
            return aged["forgotten_rows"]

        # warm every coalesce rung (a fresh engine per rung: a cold
        # arrival rate lets the pending count alone pick the bucket), two
        # whole rounds, and the refit fallback, which fires only on lost
        # positive definiteness
        hot0 = tb.hot_tenants[0]
        for rung in eng.buckets:
            e2 = FleetEngine(BankRouter(tb.bank, microbatch=8),
                             max_in_flight=2, auto_pump=False)
            for _ in range(rung):
                e2.submit(hot0, np.zeros(2, np.float32))
            e2.pump(max_blocks=1)
            e2.drain()
        for _ in range(2):
            churn_round()
        tb._bank._refit_at_slots(
            jnp.arange(cap, dtype=jnp.int32),
            jnp.zeros((cap, rows, 2), jnp.float32),
            jnp.zeros((cap, rows), jnp.float32),
            jnp.zeros((cap, rows), jnp.float32),
        )
        wd.arm()
        wd.recompiles, wd.events = 0, []
        forgot = sum(churn_round() for _ in range(2))
        wd.check("tiered-churn-final")
        assert wd.recompiles == 0 and wd.events == []
        assert forgot == 2 * cap * obs_per_tenant


# --------------------------------------------------------------------------
# the off switch: no-op defaults allocate nothing
# --------------------------------------------------------------------------


class TestNullPath:
    def test_null_registry_hands_out_the_shared_singleton(self):
        assert NULL.counter("a") is _NULL_INSTRUMENT
        assert NULL.gauge("b") is NULL.histogram("c")
        assert NULL.snapshot() == {"counters": {}, "gauges": {},
                                   "histograms": {}}

    def test_record_path_is_allocation_free(self):
        import tracemalloc
        from repro.obs import metrics as m, trace as tr
        c = NULL.counter("x")
        h = NULL.histogram("y")
        span = NULL_TRACER.span("s")
        obs_files = {m.__file__, tr.__file__}
        tracemalloc.start()
        try:
            s0 = tracemalloc.take_snapshot()
            for _ in range(2000):
                c.inc()
                c.inc(3)
                h.record(0.5)
                h.record_many((0.1, 0.2))
                with span:
                    pass
                NULL_TRACER.instant("i")
            s1 = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        leaked = [
            stat for stat in s1.compare_to(s0, "lineno")
            if stat.size_diff > 0
            and any(fr.filename in obs_files for fr in stat.traceback)
        ]
        assert leaked == [], [str(s) for s in leaked]


# --------------------------------------------------------------------------
# LatencyStats: bounded reservoir
# --------------------------------------------------------------------------


class TestLatencyReservoir:
    def test_exact_below_the_bound(self):
        stats = LatencyStats(bound=8)
        for i in range(8):
            stats.record("t", float(i))
        assert stats.samples["t"] == [float(i) for i in range(8)]
        assert stats.count("t") == 8

    def test_memory_bounded_counts_unbounded(self):
        stats = LatencyStats(bound=64, seed=1)
        n = 6400
        for i in range(n):
            stats.record("t", float(i))
        buf = stats.samples["t"]
        assert len(buf) == 64
        assert stats.count("t") == n
        # Algorithm R keeps a uniform sample of the WHOLE stream: the
        # reservoir mean sits near the stream mean, not near the tail
        assert abs(np.mean(buf) - (n - 1) / 2) < 900
        p50, _ = stats.percentiles("t")
        assert abs(p50 - n / 2) < 1500

    def test_bound_validation_and_timeouts_separate(self):
        with pytest.raises(ValueError):
            LatencyStats(bound=0)
        stats = LatencyStats(bound=4)
        stats.record("t", 0.01)
        stats.record_timeout("t")
        assert stats.count("t") == 1 and stats.timeouts["t"] == 1


# --------------------------------------------------------------------------
# instrumented engine end-to-end
# --------------------------------------------------------------------------


class TestEngineTelemetry:
    def test_engine_publishes_counters_and_spans(self):
        bank = _fleet(4, 8, 2, 4)
        reg, tracer = MetricsRegistry(), Tracer()
        router = BankRouter(bank, microbatch=8, metrics=reg, tracer=tracer)
        eng = FleetEngine(router, auto_pump=False, metrics=reg,
                          tracer=tracer)
        for i in range(16):
            eng.submit(i % 4, np.zeros(2, np.float32))
        eng.pump(max_blocks=1)
        out = eng.drain()
        assert len(out) == 16 and all(r.ok for r in out.values())
        m = eng.metrics()
        snap = m["registry"]
        assert snap["counters"]["serve_admitted_total"] == 16
        assert sum(
            v for k, v in snap["counters"].items()
            if k.startswith("serve_dispatch_blocks_total")
        ) >= 1
        names = {e["name"] for e in tracer.events()}
        assert {"bucket_select", "coalesce", "dispatch", "device_wait",
                "harvest"} <= names

    def test_instrumented_engine_answers_bit_identically(self):
        """Telemetry observes and never computes: an engine wired to a
        live registry, a recording tracer and an armed watchdog answers
        the same traffic bit-identically to one on the null objects."""
        bank = _fleet(8, 8, 2, 4)
        rng = np.random.default_rng(3)
        tenants = [int(t) for t in rng.integers(0, 8, 200)]
        Xq = rng.uniform(-1, 1, (200, 2)).astype(np.float32)

        def serve(**obs):
            router = BankRouter(bank, microbatch=8,
                                metrics=obs.get("metrics"),
                                tracer=obs.get("tracer"))
            # auto_pump=False: the pending depth alone picks each bucket,
            # so both passes dispatch the same blocks
            eng = FleetEngine(router, max_in_flight=4, max_coalesce=4,
                              auto_pump=False, **obs)
            tks = [eng.submit(t, x) for t, x in zip(tenants, Xq)]
            out = eng.drain()
            assert all(out[t].ok for t in tks)
            return (eng, np.array([out[t].mu for t in tks]),
                    np.array([out[t].var for t in tks]))

        _, mu0, var0 = serve()
        reg, tracer = MetricsRegistry(), Tracer()
        wd = serving_watchdog(mode="count", metrics=reg)
        wd.arm()                            # the null pass warmed it all
        # the registry holds the engine's collector weakly: keep the
        # engine alive until the snapshot has flushed its counters
        eng, mu1, var1 = serve(metrics=reg, tracer=tracer, watchdog=wd)
        np.testing.assert_array_equal(mu1, mu0)
        np.testing.assert_array_equal(var1, var0)
        assert reg.snapshot()["counters"]["serve_admitted_total"] == 200
        assert wd.recompiles == 0
        assert {"dispatch", "harvest"} <= {e["name"]
                                           for e in tracer.events()}
        del eng

    def test_unwired_engine_reports_empty_registry(self):
        bank = _fleet(4, 8, 2, 4)
        eng = FleetEngine(BankRouter(bank, microbatch=8))
        eng.submit(0, np.zeros(2, np.float32))
        eng.drain()
        assert eng.metrics()["registry"] == {
            "counters": {}, "gauges": {}, "histograms": {}}


# --------------------------------------------------------------------------
# checkpoint store telemetry (process-default registry)
# --------------------------------------------------------------------------


class TestStoreTelemetry:
    def test_dead_writer_staging_dirs_reaped_and_counted(self, tmp_path):
        from repro.checkpoint import store
        reg = MetricsRegistry()
        prev = set_default(reg)
        try:
            d = tmp_path / "ck"
            d.mkdir()
            child = subprocess.Popen([sys.executable, "-c", "pass"])
            child.wait()
            (d / f"tmp.3.{child.pid}").mkdir()     # verifiably dead writer
            (d / f"tmp.4.{os.getpid()}").mkdir()   # OUR pid: never touched
            assert store.latest_step(d) is None
            assert not (d / f"tmp.3.{child.pid}").exists()
            assert (d / f"tmp.4.{os.getpid()}").exists()
            snap = reg.snapshot()
            assert snap["counters"][
                "checkpoint_stale_tmp_reaped_total"] == 1
        finally:
            set_default(prev)

    def test_async_failure_counted_at_failure_time(self, tmp_path):
        from repro.checkpoint.store import AsyncCheckpointer
        reg = MetricsRegistry()
        prev = set_default(reg)
        try:
            blocked = tmp_path / "ckpt"
            blocked.write_text("a file where the dir should go")
            ac = AsyncCheckpointer(blocked)
            ac.save(1, {"w": np.zeros(2, np.float32)})
            if ac._thread is not None:
                ac._thread.join()              # failure already counted...
            assert reg.snapshot()["counters"][
                "checkpoint_async_failures_total"] == 1
            with pytest.raises(Exception):
                ac.wait()                      # ...and raised exactly once
            ac.wait()
        finally:
            set_default(prev)
