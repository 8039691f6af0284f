"""Program spans on the profiler's clock.

``GP.fit`` and ``GP.mean_var`` open ``repro.obs.span``s (a bare
``jax.profiler.TraceAnnotation``) at fixed points of the FAGP pipeline, and
every ``repro.obs.Tracer`` span brackets one of the same name.  Under a
running profiler they land on the ``/host:CPU`` plane of the
``.xplane.pb``, on the clock the device planes share; the benchmark's
per-layer metrics read them there (``bench/layers/*_host_ms.py``, ...).

Pinned here, on the CPU at toy size, for both built-in backends:

* every span name of the pipeline is recorded, exactly as written;
* each child lies inside its parent, and siblings follow one another;
* each child wraps the dispatch of the program it is named for (JAX's own
  ``PjitFunction(<name>)`` host event);
* no span carries arguments: the profiler parses a ``name#key=value#``
  suffix into the event's stats, and a span with arguments would give
  every call a different name in any trace viewer;
* a ``Tracer`` span lands both in ``Tracer.events()`` and in the trace;
* ``fagp.posterior.unprepared`` (the serving solve inverting the factor's
  diagonal blocks in-call) opens inside ``fagp.posterior`` once per
  ``mean_var`` on a state without prepared blocks, and never on a state
  from ``GP.fit``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gp import GP, GPSpec
from repro.obs import Tracer, span

# parent -> its children, in the order they run
CHILDREN = {
    "gp.fit": ("fagp.fit.prepare", "fagp.system", "fagp.factor"),
    "gp.mean_var": ("fagp.features", "fagp.posterior"),
}
NAMES = tuple(CHILDREN) + tuple(c for cs in CHILDREN.values() for c in cs)
# child span -> the jitted function it dispatches, per backend
DISPATCHES = {
    "pallas": {"fagp.system": "_pallas_system", "fagp.factor": "_factor",
               "fagp.features": "expansion_phi",
               "fagp.posterior": "_posterior_mean_var"},
    "jnp": {"fagp.system": "_jnp_system", "fagp.factor": "_factor",
            "fagp.features": "_features_jit",
            "fagp.posterior": "_posterior_mean_var"},
}


def _host_events(fn, tmp_path):
    """Run ``fn()`` under the profiler; the host plane's events as
    (name, start_ns, end_ns, stats)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    prof = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns, list(e.stats))
            for plane in prof.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.fixture(scope="module", params=["pallas", "jnp"])
def gp_events(request, tmp_path_factory):
    """Host events of one warm ``GP.fit`` and one ``GP.mean_var`` at
    p = 2, n = 4 on the given backend."""
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.uniform(-1, 1, (64, 2)), jnp.float32)
    y = jnp.sin(3 * X[:, 0]) * jnp.cos(2 * X[:, 1])
    Xs = jnp.asarray(rng.uniform(-1, 1, (16, 2)), jnp.float32)
    spec = GPSpec.create(4, eps=[0.8, 0.8], rho=2.0, noise=0.05,
                         backend=request.param)

    def work():
        gp = GP.fit(X, y, spec)
        jax.block_until_ready(gp.mean_var(Xs))

    work()                                  # compile outside the trace
    return request.param, _host_events(work,
                                       tmp_path_factory.mktemp(request.param))


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_every_pipeline_span_is_recorded(gp_events):
    _, gp_events = gp_events
    for name in NAMES:
        assert len(_named(gp_events, name)) == 1, name


def test_children_lie_inside_their_parent_in_order(gp_events):
    _, gp_events = gp_events
    for parent, children in CHILDREN.items():
        (_, p0, p1, _) = _named(gp_events, parent)[0]
        prev_end = p0
        for child in children:
            (_, c0, c1, _) = _named(gp_events, child)[0]
            assert p0 <= c0 and c1 <= p1, (parent, child)
            assert c1 - c0 < p1 - p0, (parent, child)
            assert c0 >= prev_end, child    # siblings do not overlap
            prev_end = c1


def test_each_span_wraps_its_programs_dispatch(gp_events):
    backend, gp_events = gp_events
    for child, fn in DISPATCHES[backend].items():
        (_, c0, c1, _) = _named(gp_events, child)[0]
        launches = _named(gp_events, f"PjitFunction({fn})")
        assert launches, fn
        assert all(c0 <= s and e <= c1 for _, s, e, _ in launches), \
            (child, fn)


def test_spans_carry_no_arguments(gp_events):
    _, gp_events = gp_events
    for name, _, _, stats in gp_events:
        if name.split("#")[0] in NAMES:
            assert "#" not in name and not stats, (name, stats)


def test_fitted_state_serves_prepared(gp_events):
    _, gp_events = gp_events
    assert not _named(gp_events, "fagp.posterior.unprepared")


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_unprepared_state_opens_its_span_once_per_call(backend, tmp_path):
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.uniform(-1, 1, (64, 2)), jnp.float32)
    y = jnp.sin(3 * X[:, 0])
    Xs = jnp.asarray(rng.uniform(-1, 1, (16, 2)), jnp.float32)
    spec = GPSpec.create(4, eps=[0.8, 0.8], rho=2.0, noise=0.05,
                         backend=backend)
    gp = GP.fit(X, y, spec)
    bare = GP.from_state(dataclasses.replace(gp.state, chol_dinv=None))
    jax.block_until_ready(bare.mean_var(Xs))          # compile outside

    def work():
        for _ in range(3):
            jax.block_until_ready(bare.mean_var(Xs))

    events = _host_events(work, tmp_path)
    inner = _named(events, "fagp.posterior.unprepared")
    outer = _named(events, "fagp.posterior")
    assert len(inner) == len(outer) == len(_named(events, "gp.mean_var")) == 3
    for (_, c0, c1, _), (_, p0, p1, _) in zip(sorted(inner, key=lambda e: e[1]),
                                              sorted(outer, key=lambda e: e[1])):
        assert p0 <= c0 and c1 <= p1


def test_tracer_span_lands_in_events_and_profiler_trace(tmp_path):
    tracer = Tracer()

    def work():
        with tracer.span("obs.test.tracer", bucket=64):
            with span("obs.test.bare"):
                pass

    events = _host_events(work, tmp_path)
    (ev,) = tracer.events()
    assert ev["name"] == "obs.test.tracer" and ev["args"] == {"bucket": 64}
    # the profiler gets the name alone: the args stay in the buffer
    (outer,) = _named(events, "obs.test.tracer")
    (inner,) = _named(events, "obs.test.bare")
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert outer[3] == [] and inner[3] == []
