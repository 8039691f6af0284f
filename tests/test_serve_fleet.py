"""``serve_fleet`` and the ``serve_gp --fleet`` CLI, end to end on the CPU.

Tiny fleets (8 tenants, 2 rounds, jnp backend) through every shape the
operator's loop takes: a resident bank, a tiered bank that pages cold
tenants in, sliding-window aging before re-optimization, stale-tenant
re-optimization on a resident bank, the refusals of incompatible
options, and the CLI writing a Chrome-trace JSONL that
``tools/check_trace.py`` accepts.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import pytest

from repro.launch.serve_gp import main, serve_fleet

ROOT = Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location(
    "check_trace", ROOT / "tools" / "check_trace.py")
check_trace_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_trace_mod)

# every serving stage a resident fleet's trace must show
SERVING_STAGES = ("bucket_select", "coalesce", "dispatch", "device_wait",
                  "harvest", "ingest")

TINY = dict(tenants=8, n_train=16, p=2, n=4, rounds=2,
            queries_per_round=32, observations_per_round=32, microbatch=8,
            ingest_chunk=4)


def _served_every_query(out):
    assert len(out["rounds"]) == TINY["rounds"]
    for h in out["rounds"]:
        assert h["timeouts"] == 0
        assert h["rows_absorbed"] > 0
        assert math.isfinite(h["rmse"]) and h["rmse"] < 1.0, h
    overall = out["latency"]["overall"]
    assert overall["completed"] == TINY["rounds"] * TINY["queries_per_round"]
    assert overall["expired"] == 0


def test_resident_fleet():
    out = serve_fleet(**TINY)
    _served_every_query(out)
    assert out["tenants"] == 8 and out["M"] == 16
    assert "lifecycle" not in out and "shards" not in out
    assert all(h["reopt_tenants"] == 0 for h in out["rounds"])


def test_tiered_fleet_pages_cold_tenants(tmp_path):
    """capacity < tenants: half the fleet starts cold, and the mixed
    traffic pages cold tenants in (evicting others) on the way."""
    out = serve_fleet(**TINY, cold_dir=str(tmp_path / "cold"), capacity=4)
    _served_every_query(out)
    lc = out["lifecycle"]
    assert lc["capacity"] == 4 and lc["hot"] == 4 and lc["cold"] == 4
    assert lc["warm_restores"] > 0 and lc["evictions"] > 0
    assert lc["downdated_rows"] == 0


def test_tiered_window_ages_stale_tenants(tmp_path):
    """window > 0: stale tenants forget everything older than their
    newest ``window`` rows before each re-optimization."""
    out = serve_fleet(**TINY, cold_dir=str(tmp_path / "cold"), capacity=4,
                      window=12, reopt_every=1, reopt_min_rows=2,
                      reopt_steps=3, reopt_restarts=1)
    _served_every_query(out)
    aged = sum(h["aged_rows"] for h in out["rounds"])
    assert aged > 0
    assert (out["lifecycle"]["downdated_rows"]
            + out["lifecycle"]["refit_fallbacks"]) > 0
    assert any(h["reopt_tenants"] > 0 for h in out["rounds"])


def test_reopt_every_reoptimizes_stale_tenants():
    out = serve_fleet(**TINY, reopt_every=1, reopt_min_rows=2,
                      reopt_steps=3, reopt_restarts=1)
    _served_every_query(out)
    reopt = [h["reopt_tenants"] for h in out["rounds"]]
    assert all(r > 0 for r in reopt), reopt
    assert all(h["aged_rows"] == 0 for h in out["rounds"])


@pytest.mark.parametrize("kw, match", [
    (dict(capacity=4), "cold tier"),
    (dict(window=12), "cold tier"),
    (dict(shards=2, reopt_every=1), "homogeneous"),
])
def test_incompatible_options_are_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        serve_fleet(**TINY, **kw)


def test_cli_trace_passes_check_trace(tmp_path, monkeypatch, capsys):
    # a set cache variable leaves JAX's config alone (compile_cache.py)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    trace = tmp_path / "trace.jsonl"
    main(["--fleet", "4", "--n-train", "16", "--n", "4", "--rounds", "2",
          "--update-size", "16", "--queries", "16", "--microbatch", "8",
          "--trace-out", str(trace)])
    printed = capsys.readouterr().out
    assert "fleet of 4 fitted" in printed and "round 1:" in printed
    assert check_trace_mod.check_trace(trace, expect=SERVING_STAGES) == []
