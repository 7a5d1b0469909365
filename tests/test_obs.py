"""Observability layer tests (ISSUE 10 / DESIGN.md 1j).

Covers the four obs surfaces and their acceptance bars:

* histogram quantile estimates pinned against numpy order statistics
  (within one bucket factor — the documented estimator contract);
* snapshot/delta/reset coherence, including two services interleaving
  publishes into the shared registry;
* span nesting and Chrome-trace export schema (Perfetto-loadable), for a
  real ``PairwiseService.similarity`` request;
* the comm-ledger reconciler: measured/predicted exactly 1.0 on the
  unreplicated executors, exactly r on the coded executor (r=2 measured
  assembly bytes matching ``coded_assembly_model`` under a real 8-device
  mesh, in a subprocess), anomaly events on drift;
* the FUSED_STATS shared-dict hazard regression: the default registry
  fused executor owns instance-scoped stats, while ``engine.fused_stats``
  stays live as the aggregate view;
* cache eviction events from the jit/block/plan caches.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import PLAN_CACHE, plan_a2a, plan_x2y
from repro.mapreduce import engine as mr_engine
from repro.mapreduce import get_executor, pairwise_similarity
from repro.obs import EVENTS, LEDGER, REGISTRY, TRACER
from repro.obs.metrics import Histogram, MetricsRegistry, \
    exponential_buckets


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test sees a clean slate and leaves one behind (the registry /
    ledger / tracer are process-global by design)."""
    obs.reset_all()
    obs.configure(enabled=True)
    yield
    obs.reset_all()
    obs.configure(enabled=True)


def _cold_plans():
    """Drop the plan cache and its remapped schemas, so a request on a
    profile an earlier test served plans and lowers anew."""
    PLAN_CACHE.clear()


def _zipf_table(m=64, d=8, q=1.0, seed=0):
    rng = np.random.default_rng(seed)
    w = np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45 * q)
    x = rng.normal(size=(m, d)).astype(np.float32)
    return x, w


# ---------------------------------------------------------------- histograms
def test_histogram_quantiles_vs_numpy():
    """p50/p90/p99 within one bucket factor of numpy's exact order
    statistics on a lognormal sample (fixed seed)."""
    rng = np.random.default_rng(42)
    sample = rng.lognormal(mean=-3.0, sigma=1.0, size=5000)
    h = Histogram()
    for v in sample:
        h.observe(float(v))
    factor = 1.25                      # DEFAULT_BUCKETS growth factor
    for q in (0.50, 0.90, 0.99):
        exact = float(np.quantile(sample, q))
        est = h.quantile(q)
        assert exact / factor <= est <= exact * factor, (q, est, exact)
    assert h.count == 5000
    assert h.mean == pytest.approx(sample.mean(), rel=1e-9)
    assert h.max == pytest.approx(sample.max())
    assert h.min == pytest.approx(sample.min())


def test_histogram_overflow_and_empty():
    h = Histogram(bounds=exponential_buckets(1.0, 2.0, 4))  # ..., 8.0
    assert h.quantile(0.5) == 0.0      # empty
    h.observe(100.0)                   # overflow bucket
    assert h.quantile(0.5) == 100.0    # overflow reports tracked max
    assert h.summary()["p99"] == 100.0


def test_registry_snapshot_delta_reset():
    r = MetricsRegistry()
    r.counter("req", executor="fused").inc()
    r.counter("req", executor="dense").inc(3)
    r.gauge("load", executor="fused").set(0.5)
    r.histogram("lat", executor="fused").observe(0.01)
    before = r.snapshot()
    r.counter("req", executor="fused").inc(2)
    r.histogram("lat", executor="fused").observe(0.02)
    after = r.snapshot()

    d = MetricsRegistry.delta(before, after)
    assert d["counters"] == {"req{executor=fused}": 2}
    assert d["histograms"]["lat{executor=fused}"]["count"] == 1
    assert r.counter_total("req") == 6
    assert r.counter_total("req", executor="dense") == 3

    r.reset()
    snap = r.snapshot()
    assert snap["counters"]["req{executor=fused}"] == 0
    assert snap["histograms"]["lat{executor=fused}"]["count"] == 0


def test_kill_switch_disables_all_surfaces():
    prior = obs.enabled()
    try:
        obs.configure(enabled=False)
        REGISTRY.counter("dead").inc()
        REGISTRY.histogram("dead_h").observe(1.0)
        with obs.span("dead_span") as s:
            assert s is None
        assert EVENTS.emit("dead_event") is None
        assert LEDGER.record(
            executor="x", workload="y", predicted_rows=1.0, lb_rows=1.0,
            plan_slots=1, measured_slots=1, d=1) is None
        assert REGISTRY.counter("dead").value == 0
        assert len(TRACER.spans()) == 0
    finally:
        obs.configure(enabled=prior)


# -------------------------------------------------------------------- spans
def test_span_nesting_and_chrome_trace_schema():
    with obs.span("outer", workload="pairs") as outer:
        with obs.span("inner") as inner:
            pass
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert outer.duration >= inner.duration >= 0.0

    doc = TRACER.chrome_trace()
    text = json.dumps(doc)             # must be JSON-serializable
    doc = json.loads(text)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"
        for key in ("name", "ts", "dur", "pid", "tid", "args"):
            assert key in ev, ev
    by_name = {ev["name"]: ev for ev in evs}
    assert by_name["inner"]["args"]["parent"] == \
        by_name["outer"]["args"]["span_id"]
    assert by_name["outer"]["args"]["workload"] == "pairs"


def test_service_request_trace_exports(tmp_path):
    """A real PairwiseService.similarity request produces a schema-valid
    Chrome trace with the documented span hierarchy."""
    from repro.serve import PairwiseService

    x, w = _zipf_table()
    svc = PairwiseService(q=1.0, executor="fused")
    svc.similarity(x, weights=w)

    path = tmp_path / "trace.json"
    TRACER.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    names = [ev["name"] for ev in doc["traceEvents"]]
    assert "request" in names
    assert "plan" in names and "execute" in names
    by_name = {ev["name"]: ev for ev in doc["traceEvents"]}
    # plan and execute nest under the request span
    req_id = by_name["request"]["args"]["span_id"]
    assert by_name["plan"]["args"]["parent"] == req_id
    assert by_name["execute"]["args"]["parent"] == req_id
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X" and ev["dur"] >= 0


# ------------------------------------------------------- host-path spans
def _tree(spans):
    """``{span_id: "request > execute > maps", ...}`` paths of the ring."""
    byid = {s.span_id: s for s in spans}

    def path(s):
        names = []
        while s is not None:
            names.append(s.name)
            s = byid.get(s.parent_id)
        return " > ".join(reversed(names))
    return {s.span_id: path(s) for s in spans}


def _by_path(spans):
    paths = _tree(spans)
    out: dict = {}
    for s in spans:
        out.setdefault(paths[s.span_id], []).append(s)
    return out


def _block_service():
    from repro.serve import PairwiseService
    rng = np.random.default_rng(3)
    table = rng.normal(size=(600, 8)).astype(np.float32)
    svc = PairwiseService(q=1.0, executor="fused")
    svc.load_block_table(table, weights=np.full(600, 0.02))
    return svc, table


@pytest.mark.parametrize("kind", ["similarity", "block"])
def test_request_span_tree(kind):
    """A fused request splits into request > plan > lower and request >
    execute > {maps, upload, launch}, with the documented attributes."""
    from repro.serve import PairwiseService
    _cold_plans()
    if kind == "similarity":
        x, w = _zipf_table()
        svc = PairwiseService(q=1.0, executor="fused")
        TRACER.clear()
        svc.similarity(x, weights=w)
        what = "pairs"
    else:
        svc, _table = _block_service()
        TRACER.clear()
        svc.block(0, 96, 200, 296)
        what = "block"
    got = _by_path(TRACER.spans())
    (req,) = got["request"]
    assert req.attrs["workload"] == what
    assert isinstance(req.attrs["compiles"], int)
    (table_up,) = got["request > upload"]
    assert table_up.attrs["bytes"] > 0
    (lower,) = got["request > plan > lower"]
    assert lower.attrs["cached"] is False
    assert len(got["request > execute"]) == 1
    (maps,) = got["request > execute > maps"]
    assert maps.attrs == {"what": "srcmap", "cached": False}
    assert got["request > execute > upload"]
    assert all(s.attrs["bytes"] > 0
               for s in got["request > execute > upload"])
    # the kernel's program; a block's slices of the table are one more
    launches = got["request > execute > launch"]
    assert len(launches) == (1 if kind == "similarity" else 2)
    assert all(isinstance(s.attrs["compiles"], int) for s in launches)
    # every span of the request is in the tree above, and at most ten;
    # a similarity request plans its schema under ``plan`` (a block's
    # schema was planned when its table was loaded)
    paths = {
        "request", "request > upload", "request > plan",
        "request > plan > lower", "request > execute",
        "request > execute > maps", "request > execute > upload",
        "request > execute > launch"}
    if kind == "similarity":
        (schema,) = got["request > plan > schema"]
        assert schema.attrs == {"family": "a2a", "cached": False}
        paths.add("request > plan > schema")
    assert set(got) == paths
    assert len(TRACER.spans()) <= 10
    # spans time host work: children fit inside their parent
    parent = {s.span_id: s for s in TRACER.spans()}
    for s in TRACER.spans():
        if s.parent_id is not None:
            assert s.duration <= parent[s.parent_id].duration


def test_same_schema_twice_reads_cached():
    """Passing one ``schema`` object twice finds its plan and source map
    on the second request: ``lower`` and ``maps`` read ``cached=True``."""
    _cold_plans()
    x, w = _zipf_table()
    schema = plan_a2a(w, 1.0)
    for want in (False, True):
        TRACER.clear()
        pairwise_similarity(x, q=1.0, schema=schema, executor="fused")
        got = _by_path(TRACER.spans())
        (lower,) = got["plan > lower"]
        (maps,) = got["execute > maps"]
        assert lower.attrs["cached"] is want
        assert maps.attrs["cached"] is want


def test_repeat_weights_read_cached():
    """A second request with the same weight vector gets the same schema
    from ``plan_a2a``: ``lower`` and ``maps`` read ``cached=True``, and the
    answer is the first one's and the float64 reference's."""
    from repro.serve import PairwiseService
    _cold_plans()
    x, w = _zipf_table()
    m = x.shape[0]
    svc = PairwiseService(q=1.0, executor="fused")
    answers = []
    for want in (False, True):
        TRACER.clear()
        sims, _info = svc.similarity(x, weights=w.copy())
        answers.append(np.asarray(sims))
        got = _by_path(TRACER.spans())
        (lower,) = got["request > plan > lower"]
        (maps,) = got["request > execute > maps"]
        assert lower.attrs["cached"] is want
        assert maps.attrs["cached"] is want
    np.testing.assert_array_equal(answers[1], answers[0])
    x64 = x.astype(np.float64)
    ref = x64 @ x64.T * (1 - np.eye(m))
    np.testing.assert_allclose(answers[1], ref, rtol=1e-5, atol=1e-5)


def test_x2y_repeat_weights_read_cached():
    """A repeat x2y request with the same weights finds its rect plan and
    rect source map again: ``schema``, ``lower`` and ``maps`` read
    ``cached=True``; the answer is the first one's and the float64
    reference's."""
    from repro.serve import PairwiseService
    _cold_plans()
    x, wx, y, wy = _zipf_pair()
    svc = PairwiseService(q=1.0, executor="fused")
    answers = []
    for want in (False, True):
        TRACER.clear()
        sims, _info = svc.x2y(x, y, wx=wx.copy(), wy=wy.copy())
        answers.append(np.asarray(sims))
        got = _by_path(TRACER.spans())
        for path in ("request > plan > schema", "request > plan > lower",
                     "request > execute > maps"):
            (s,) = got[path]
            assert s.attrs["cached"] is want, path
    np.testing.assert_array_equal(answers[1], answers[0])
    ref = x.astype(np.float64) @ y.astype(np.float64).T
    np.testing.assert_allclose(answers[1], ref, rtol=1e-5, atol=1e-5)


def test_schema_memo_counters():
    """``cache.hits``/``cache.misses`` with ``cache="schema"`` count the
    remapped-schema memo beside the ``cache="plan"`` series."""
    _cold_plans()
    _x, w = _zipf_table()
    plan_a2a(w, 1.0)                              # plan miss, schema miss
    plan_a2a(w, 1.0)                              # plan hit, schema hit
    plan_a2a(w[::-1], 1.0)                        # plan hit, schema miss
    plan_a2a(w, 1.0, use_cache=False)             # neither
    assert REGISTRY.counter_total("cache.misses", cache="schema") == 2
    assert REGISTRY.counter_total("cache.hits", cache="schema") == 1
    assert REGISTRY.counter_total("cache.misses", cache="plan") == 1
    assert REGISTRY.counter_total("cache.hits", cache="plan") == 2


CODED_REPEAT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    assert len(jax.devices()) == 4, jax.devices()
    from repro.obs import TRACER
    from repro.serve import PairwiseService

    rng = np.random.default_rng(0)
    m = 64
    w = np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45)
    x = rng.normal(size=(m, 8)).astype(np.float32)
    svc = PairwiseService(q=1.0, executor="coded",
                          executor_options={"replication": 1})
    answers, cached = [], []
    for _ in range(2):
        TRACER.clear()
        sims, _ = svc.similarity(x, weights=w)
        answers.append(np.asarray(sims))
        cached.append(sorted((s.name, s.attrs.get("what", ""),
                              s.attrs["cached"])
                             for s in TRACER.spans()
                             if s.name in ("lower", "maps")))
    print("FIRST", cached[0])
    print("SECOND", cached[1])
    assert [c for *_, c in cached[0]] == [False] * 4, cached[0]
    assert cached[1] == [("lower", "", True), ("maps", "coded_maps", True),
                         ("maps", "groups", True),
                         ("maps", "partition", True)], cached[1]
    np.testing.assert_array_equal(answers[1], answers[0])
    ref = x.astype(np.float64) @ x.astype(np.float64).T * (1 - np.eye(m))
    np.testing.assert_allclose(answers[1], ref, rtol=1e-5, atol=1e-5)
    print("CODED_REPEAT_OK")
""")


def test_coded_repeat_reads_cached_on_4_devices():
    """On four CPU devices, a repeat request to the coded executor finds
    its partition, groups and coded maps on the shared plan: each ``maps``
    span reads ``cached=True`` (subprocess: the main test process keeps
    its default device count)."""
    res = subprocess.run(
        [sys.executable, "-c", CODED_REPEAT_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
             "HOME": os.environ.get("HOME", "/tmp")},
    )
    assert "CODED_REPEAT_OK" in res.stdout, res.stdout + res.stderr


def _zipf_pair(mx=48, my=40, d=8, seed=0):
    """An X and a Y table with Zipf sizes on both sides (q = 1.0)."""
    x, wx = _zipf_table(m=mx, d=d, seed=seed)
    y, wy = _zipf_table(m=my, d=d, seed=seed + 1)
    return x, wx, y, wy


@pytest.mark.parametrize("kind", ["similarity", "x2y"])
def test_one_schema_span_per_request(kind):
    """Each request plans its schema under one ``schema`` span nested in
    ``plan``: ``family`` names the planner, ``cached`` reads False on the
    first request and True on a repeat of the same weights."""
    from repro.serve import PairwiseService
    _cold_plans()
    svc = PairwiseService(q=1.0, executor="fused")
    if kind == "similarity":
        x, w = _zipf_table()
        serve = lambda: svc.similarity(x, weights=w.copy())  # noqa: E731
        family = "a2a"
    else:
        x, wx, y, wy = _zipf_pair()
        serve = lambda: svc.x2y(x, y, wx=wx.copy(), wy=wy.copy())  # noqa
        family = "x2y"
    for want in (False, True):
        TRACER.clear()
        serve()
        got = _by_path(TRACER.spans())
        (schema,) = got["request > plan > schema"]
        assert schema.attrs == {"family": family, "cached": want}
        assert sum(s.name == "schema" for s in TRACER.spans()) == 1


def test_schema_span_records_with_obs_on_only():
    """The ``schema`` span records with the profiler's annotations off and
    on, and not at all with observability off."""
    _cold_plans()
    _x, wx, _y, wy = _zipf_pair()
    for annotate in (False, True):
        TRACER.clear()
        TRACER.annotate = annotate
        try:
            plan_x2y(wx, wy, 1.0)
        finally:
            TRACER.annotate = False
        assert [s.name for s in TRACER.spans()] == ["schema"]
    obs.configure(enabled=False)
    TRACER.clear()
    plan_x2y(wx, wy, 1.0)
    assert TRACER.spans() == []


def _plan_block_bytes(plan) -> int:
    """Σ over the plan's buckets of reducers x X width x Y width x 4."""
    return sum(b.idx.shape[0] * b.idx.shape[1]
               * (b.idx.shape[1] if b.yidx is None else b.yidx.shape[1]) * 4
               for b in plan.buckets)


@pytest.mark.parametrize("kind", ["similarity", "x2y"])
def test_block_bytes_counter_equals_the_plan(kind):
    """``ledger.block_bytes`` (the counter, the ledger record and the
    ``execute`` span's ``block_bytes``) is Σ R·Lx·Ly·4 over the plan's
    buckets, on a pairs and on an x2y request."""
    from repro.serve import PairwiseService
    _cold_plans()
    svc = PairwiseService(q=1.0, executor="fused")
    if kind == "similarity":
        x, w = _zipf_table()
        svc.similarity(x, weights=w)
        plan = mr_engine.build_plan(plan_a2a(w, 1.0))
    else:
        x, wx, y, wy = _zipf_pair()
        svc.x2y(x, y, wx=wx, wy=wy)
        plan = mr_engine.build_x2y_plan(plan_x2y(wx, wy, 1.0), len(wx))
    want = _plan_block_bytes(plan)
    assert want > 0
    assert REGISTRY.counter_total("ledger.block_bytes") == want
    assert LEDGER.last().block_bytes == want
    (execute,) = [s for s in TRACER.spans() if s.name == "execute"]
    assert execute.attrs["block_bytes"] == want


@pytest.mark.parametrize("name", ["dense", "sharded", "coded"])
def test_block_bytes_on_other_executors(name):
    """The dense executor writes its (R, L, L) rows; the sharded and coded
    executors their stacked shard groups, every shard's rows counted."""
    x, w = _zipf_table()
    ex = get_executor(name)
    _sims, plan, _ = pairwise_similarity(x, q=1.0, weights=w, executor=ex)
    if name == "dense":
        want = plan.R * plan.L * plan.L * 4
    else:
        cache = ("_shard_groups_cache" if name == "sharded"
                 else "_coded_groups_cache")
        (groups,) = plan.__dict__[cache].values()
        want = sum(g[0].shape[0] * g[0].shape[1] * g[0].shape[2] ** 2 * 4
                   for g in groups)
    assert LEDGER.last().block_bytes == want
    assert REGISTRY.counter_total("ledger.block_bytes",
                                  executor=name) == want


def test_block_repeat_reads_cached():
    """A block served twice takes its sub-plan and source map from the
    caches the second time."""
    svc, _table = _block_service()
    svc.block(0, 96, 200, 296)
    TRACER.clear()
    svc.block(0, 96, 200, 296)
    got = _by_path(TRACER.spans())
    assert got["request > plan > lower"][0].attrs["cached"] is True
    assert got["request > execute > maps"][0].attrs["cached"] is True


def test_upload_bytes_equal_the_arrays_put():
    """The ``upload`` spans' ``bytes`` add up to the host arrays a request
    puts, counted here from the table and the plan: the table, then at
    one site each bucket's slot ids and masks (int32, bool; once, though
    the self-join reads them on both sides) and the (m, m) int32 source
    map."""
    from repro.serve import PairwiseService
    x, w = _zipf_table()
    m, d = x.shape
    svc = PairwiseService(q=1.0, executor="fused")
    TRACER.clear()
    _sims, info = svc.similarity(x, weights=w)
    plan = mr_engine.build_plan(plan_a2a(w, 1.0))
    want = m * d * 4 + m * m * 4 + sum(
        b.idx.shape[0] * b.idx.shape[1] * (4 + 1) for b in plan.buckets)
    got = [s.attrs["bytes"] for s in TRACER.spans() if s.name == "upload"]
    assert len(got) == 2
    assert sum(got) == want

    # a block: the table, each rect bucket's X and Y ids and masks, and
    # the (bx, by) int32 source map
    svc, table = _block_service()
    i0, i1, j0, j1 = 0, 96, 200, 296
    TRACER.clear()
    svc.block(i0, i1, j0, j1)
    sub = mr_engine.block_subplan(svc._block_sparse, i0, i1, j0, j1)
    want = table.size * 4 + (i1 - i0) * (j1 - j0) * 4 + sum(
        b.idx.size * 5 + b.yidx.size * 5 for b in sub.buckets)
    got = [s.attrs["bytes"] for s in TRACER.spans() if s.name == "upload"]
    assert len(got) == 2
    assert sum(got) == want


def test_coded_spans_name_each_map():
    """The coded executor's host maps each get a ``maps`` span, and its
    one upload site puts the groups, the send map and the source map."""
    from repro.mapreduce import make_executor
    _cold_plans()
    x, w = _zipf_table()
    m = x.shape[0]
    TRACER.clear()
    _sims, plan, _schema = pairwise_similarity(
        x, q=1.0, weights=w, executor=make_executor("coded", replication=1))
    got = _by_path(TRACER.spans())
    whats = [s.attrs["what"] for s in got["execute > maps"]]
    assert whats == ["partition", "groups", "coded_maps"]
    (groups,) = plan.__dict__["_coded_groups_cache"].values()
    ((sendmap, srcmap, _stats),) = plan.__dict__["_coded_maps_cache"].values()
    assert srcmap.nbytes == m * m * 4          # one shard owns every row
    (up,) = got["execute > upload"]
    assert up.attrs["bytes"] == sendmap.nbytes + srcmap.nbytes + sum(
        a.nbytes for g in groups for a in g[:4])
    assert len(got["execute > launch"]) == 1


def test_request_compiles_first_shape_then_none():
    """``compiles`` on the request span (and in ``info``) counts backend
    compiles: above 0 for a table shape this process has not served, 0
    when the same request comes again."""
    from repro.serve import PairwiseService
    x, w = _zipf_table(m=43, d=13)
    svc = PairwiseService(q=1.0, executor="fused")
    seen = []
    for _ in range(2):
        TRACER.clear()
        _sims, info = svc.similarity(x, weights=w)
        (req,) = [s for s in TRACER.spans() if s.name == "request"]
        assert req.attrs["compiles"] == info["compiles"]
        seen.append(info["compiles"])
    assert seen[0] > 0 and seen[1] == 0
    assert REGISTRY.counter_total("jit.compiles") >= seen[0]


def test_tracer_counts_dropped_spans():
    """A full ring counts what it pushes out (``dropped`` and the
    ``obs.spans_dropped`` counter); ``clear`` starts the count anew."""
    tr = obs.Tracer(capacity=3, annotate=False)
    for i in range(5):
        with tr.span("s", i=i):
            pass
    assert [s.attrs["i"] for s in tr.spans()] == [2, 3, 4]
    assert tr.dropped == 2
    assert REGISTRY.counter_total("obs.spans_dropped") == 2
    tr.clear()
    assert tr.dropped == 0
    assert obs.Tracer()._spans.maxlen == 65536


def test_span_attributes_reach_the_profiler(monkeypatch):
    """Under ``annotate=True`` each span enters a ``TraceAnnotation`` and
    hands it the span's attributes, late ones included, as metadata."""
    import jax.profiler
    made = []

    class Recorder:
        def __init__(self, name, **kw):
            self.name, self.meta = name, dict(kw)
            made.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            self.meta.update(kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    tr = obs.Tracer(annotate=True)
    with tr.span("upload", bytes=12) as s:
        s.attrs["cached"] = True
    assert [(r.name, r.meta) for r in made] == \
        [("upload", {"bytes": 12, "cached": True})]


# ------------------------------------------------------------- comm ledger
def test_reconciler_dense_exact():
    """Dense executor: measured == planned shuffle exactly (ratio 1.0,
    zero tolerance), and measured_over_lb = comm_cost / lower_bound."""
    x, w = _zipf_table()
    sims, plan, _ = pairwise_similarity(x, q=1.0, weights=w,
                                        executor="dense")
    rec = LEDGER.last()
    assert rec is not None and rec.executor == "dense"
    assert rec.measured_over_predicted == 1.0
    assert not rec.anomaly
    assert rec.measured_over_lb == pytest.approx(
        float(plan.comm_cost) / float(plan.lower_bound))
    # gathered bytes = executed slot count x row bytes (slots are the
    # copy-count ledger; predicted_bytes is the weighted-row view)
    assert rec.gathered_bytes == rec.measured_slots * rec.d * rec.itemsize
    assert rec.measured_slots == int(np.asarray(plan.mask).sum())


@pytest.mark.parametrize("name", ["dense", "bucketed", "fused", "sharded",
                                  "coded", "streaming"])
def test_reconciler_reports_on_every_executor(name):
    """All six registry executors file a reconciliation record per
    request, with both ratios present and the ratio matching the
    executor's replication (1.0 everywhere at replication 1)."""
    x, w = _zipf_table()
    seq0 = LEDGER.seq
    pairwise_similarity(x, q=1.0, weights=w, executor=name)
    recs = [r for r in LEDGER.records(since_seq=seq0)
            if r.executor == name]
    assert recs, f"{name} filed no ledger record"
    rec = recs[-1]
    assert rec.measured_over_predicted == pytest.approx(rec.replication)
    assert rec.measured_over_lb is not None and rec.measured_over_lb >= 1.0
    assert not rec.anomaly


def test_reconciler_x2y_rectangular():
    from repro.mapreduce import x2y_similarity

    rng = np.random.default_rng(3)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    y = rng.normal(size=(20, 6)).astype(np.float32)
    seq0 = LEDGER.seq
    x2y_similarity(jnp.asarray(x), jnp.asarray(y), q=2.0)
    recs = LEDGER.records(since_seq=seq0)
    assert recs and recs[-1].workload == "x2y"
    assert recs[-1].measured_over_predicted == 1.0


def test_reconciler_anomaly_event():
    """A measured/predicted drift beyond tolerance raises an anomaly:
    flagged record, ledger.anomalies counter, comm_anomaly event."""
    rec = LEDGER.record(
        executor="dense", workload="pairs", predicted_rows=100.0,
        lb_rows=80.0, plan_slots=100, measured_slots=150, d=8)
    assert rec.anomaly
    assert rec.measured_over_predicted == 1.5
    assert REGISTRY.counter_total("ledger.anomalies", executor="dense") == 1
    evs = EVENTS.events(kind="comm_anomaly")
    assert evs and evs[-1]["measured_over_predicted"] == 1.5

    ok = LEDGER.record(
        executor="dense", workload="pairs", predicted_rows=100.0,
        lb_rows=80.0, plan_slots=100, measured_slots=100, d=8)
    assert not ok.anomaly


def test_reconciler_streaming_delta_below_lb():
    """Streaming edits ship only dirty reducers: the delta's
    measured_over_lb sits *below* 1 against the full instance's bound —
    the quantified streaming savings."""
    from repro.serve import PairwiseService

    x, w = _zipf_table(m=96)
    svc = PairwiseService(q=1.0, executor="streaming")
    svc.load_table(x, w)
    rng = np.random.default_rng(7)
    _, info = svc.add_input(rng.normal(size=(1, 8)).astype(np.float32),
                            0.1)
    comm = info.get("comm")
    assert comm is not None
    assert comm["measured_over_predicted"] == 1.0
    assert comm["measured_over_lb"] is not None
    assert comm["measured_over_lb"] < 1.0


# ------------------------------------------- coded r=2 vs analytic model
CODED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 8, jax.devices()
    from repro.core import plan_a2a
    from repro.mapreduce import pairwise_similarity
    from repro.mapreduce.executors import coded_assembly_model, \\
        make_executor
    from repro.obs import LEDGER

    rng = np.random.default_rng(0)
    m = 48
    w = np.clip(rng.zipf(1.7, m) / 24.0, 0.02, 0.45)
    x = jnp.asarray(rng.normal(size=(m, 6)).astype(np.float32))
    ex = make_executor("coded", replication=2)
    sims, plan, _ = pairwise_similarity(x, q=1.0, weights=w, executor=ex)

    recs = [r for r in LEDGER.records() if r.executor == "coded"]
    assert recs, "coded executor filed no ledger record"
    rec = recs[-1]
    # measured slots = r x planned slots, exactly
    assert rec.measured_over_predicted == 2.0, rec.summary()
    assert rec.replication == 2.0
    assert not rec.anomaly, rec.summary()
    assert rec.measured_over_lb is not None

    # measured assembly bytes match the analytic coded model exactly
    model = coded_assembly_model(plan, 8, 2, m, itemsize=4)
    got = rec.meta["assembly_bytes_per_shard"]
    want = model["assembly_bytes_per_shard"]
    assert got == want, (got, want)
    assert rec.assembled_bytes == 8 * want, rec.assembled_bytes
    print("CODED_LEDGER_OK", rec.measured_over_predicted)
""")


def test_coded_r2_reconciles_against_model_on_8_device_mesh():
    """Coded r=2 on a real 8-shard mesh: the reconciler's ratio is
    exactly 2.0 and its measured assembly bytes equal
    ``coded_assembly_model`` (subprocess: the main test process keeps its
    default device count)."""
    res = subprocess.run(
        [sys.executable, "-c", CODED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
             "HOME": os.environ.get("HOME", "/tmp")},
    )
    assert "CODED_LEDGER_OK" in res.stdout, res.stdout + res.stderr


# ------------------------------------------------------ interleaved services
def test_interleaved_services_snapshot_coherent():
    """Two services with different executors/tenants interleave requests:
    per-label series stay separate, snapshot delta accounts for exactly
    the window's requests, reset() zeroes both without breaking live
    handles."""
    from repro.serve import PairwiseService

    x, w = _zipf_table()
    a = PairwiseService(q=1.0, executor="bucketed", tenant="a")
    b = PairwiseService(q=1.0, executor="fused", tenant="b")
    a.similarity(x, weights=w)
    before = REGISTRY.snapshot()
    b.similarity(x, weights=w)
    a.similarity(x, weights=w)
    b.similarity(x, weights=w)
    after = REGISTRY.snapshot()

    d = MetricsRegistry.delta(before, after)
    key_a = "serve.requests{executor=bucketed,tenant=a,workload=pairs}"
    key_b = "serve.requests{executor=fused,tenant=b,workload=pairs}"
    assert d["counters"][key_a] == 1
    assert d["counters"][key_b] == 2
    assert after["counters"][key_a] == 2
    assert after["counters"][key_b] == 2

    REGISTRY.reset()
    b.similarity(x, weights=w)        # live handles keep publishing
    assert REGISTRY.snapshot()["counters"][key_b] == 1
    assert REGISTRY.snapshot()["counters"][key_a] == 0


# ----------------------------------------------------- FUSED_STATS regression
def test_default_fused_executor_owns_its_stats():
    """Regression (shared-dict hazard): the registry's default fused
    executor must NOT alias engine.FUSED_STATS — an Executor.reset() on
    it would have zeroed every other caller's counters."""
    ex = get_executor("fused")
    assert ex._stats is not mr_engine.FUSED_STATS


def test_fused_stats_is_aggregate_view():
    """engine.fused_stats() keeps its documented contract: a live
    aggregate over fused dispatches, including the default registry
    instance (the before/after delta used by the kernel tests)."""
    x, w = _zipf_table()
    mr_engine.reset_fused_stats()
    before = mr_engine.fused_stats()
    assert before == {"calls": 0, "kernel": 0, "streamed": 0,
                      "fallbacks": 0}
    pairwise_similarity(x, q=1.0, weights=w, executor="fused")
    after = mr_engine.fused_stats()
    assert after["calls"] == 1
    assert after["streamed"] + after["kernel"] == 1
    # instance-scoped stats saw the same dispatch
    assert get_executor("fused").stats()["calls"] >= 1


# ------------------------------------------------------------------- events
def test_jit_cache_eviction_emits_event():
    """Each jit-cache eviction bumps cache.evictions{cache=jit} and files
    a structured cache_eviction event naming the evicted key."""
    for i in range(3):
        mr_engine._JIT_CACHE[("obs_test", i)] = i
    mr_engine._evict_oldest()
    mr_engine._evict_oldest()
    assert REGISTRY.counter_total("cache.evictions", cache="jit") == 2
    evs = EVENTS.events(kind="cache_eviction")
    assert len(evs) == 2
    assert all(e["cache"] == "jit" for e in evs)
    for key in [k for k in mr_engine._JIT_CACHE
                if isinstance(k, tuple) and k and k[0] == "obs_test"]:
        del mr_engine._JIT_CACHE[key]


def test_event_log_ring_and_counts():
    for i in range(5):
        EVENTS.emit("unit_test_event", i=i)
    assert EVENTS.counts()["unit_test_event"] == 5
    tail = EVENTS.events(kind="unit_test_event", last=2)
    assert [e["i"] for e in tail] == [3, 4]
    seqs = [e["seq"] for e in EVENTS.events(kind="unit_test_event")]
    assert seqs == sorted(seqs)
