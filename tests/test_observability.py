"""Observability subsystem tests (ISSUE 8): span recorder semantics
(disabled-mode zero-allocation pin, nesting, thread correctness),
Chrome trace JSON schema, metrics registry math (histogram buckets,
quantiles, kind conflicts), the LazyScalar deferred-sync contract,
the watchdog live-span dump, the profiler re-backing, and THE
acceptance pin: one fit() + one LLMServer session + one checkpoint
save export a single merged Chrome-trace timeline while scrape()
returns dispatch, serving and checkpoint metrics from the same
process-wide registry.
"""

import gc
import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import trace


@pytest.fixture(autouse=True)
def _trace_reset():
    """Tracing is process-global: every test starts and ends disarmed
    with an empty ring so suites can run in any order.  The collector
    is held still meanwhile: armed, its collections are spans of the
    ring too (``host.gc``), and tests here pin the ring's contents."""
    trace.disable()
    trace.clear()
    gc.disable()
    yield
    gc.enable()
    trace.disable()
    trace.clear()


def _validate_chrome(obj):
    """Schema check for Chrome/Perfetto ``trace_event`` JSON (the
    subset the exporter emits): loadable by chrome://tracing and
    ui.perfetto.dev."""
    assert isinstance(obj, dict) and isinstance(
        obj.get("traceEvents"), list)
    for ev in obj["traceEvents"]:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "i", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["dur"], (int, float))
            assert ev["dur"] >= 0
        elif ev["ph"] == "i":
            assert isinstance(ev["ts"], (int, float))
            assert ev["s"] in ("t", "p", "g")
        else:                                   # M metadata
            assert ev["name"] == "thread_name"
            assert isinstance(ev["args"]["name"], str)
    json.dumps(obj)                             # serializable


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------
def test_disabled_mode_zero_allocation_pin():
    """THE overhead pin: when tracing is off, span() returns the bare
    profiler annotation — no recorder object, nothing recorded, no
    live stack — so the unconditional call sites in the hot loops cost
    a flag test in C++ while no profiler session runs."""
    import jax
    assert not trace.enabled()
    s1 = trace.span("dispatch.group")
    s2 = trace.span("anything", args={"k": 1})
    for s in (s1, s2):              # the annotation and nothing of ours
        assert type(s) is jax.profiler.TraceAnnotation
    with s1:
        with trace.span("nested"):
            pass
    trace.instant("marker")
    assert trace.events() == []     # ring untouched
    assert trace.live_spans() == {}


def test_span_recording_nesting_and_containment():
    trace.enable()
    with trace.span("outer", args={"k": 8}):
        with trace.span("inner"):
            pass
        with trace.span("inner"):
            pass
    evs = trace.events()
    assert [e[1] for e in evs] == ["inner", "inner", "outer"]
    (i1, i2, outer) = evs
    assert outer[0] == "X" and outer[5] == {"k": 8}
    # containment: both inners start after outer starts and end
    # before outer ends (same thread, one stack)
    for inner in (i1, i2):
        assert inner[3] >= outer[3]
        assert inner[3] + inner[4] <= outer[3] + outer[4]
    # summary aggregates per name
    s = trace.summary()
    assert s["inner"]["count"] == 2 and s["outer"]["count"] == 1
    assert s["inner"]["avg"] <= s["inner"]["max"] + 1e-9


def test_span_thread_correctness_and_live_stacks():
    trace.enable()
    seen = {}
    release = threading.Event()
    started = threading.Event()

    def worker():
        with trace.span("worker.phase"):
            with trace.span("worker.subphase"):
                started.set()
                release.wait(10)

    t = threading.Thread(target=worker, name="obs-worker")
    t.start()
    assert started.wait(10)
    with trace.span("main.phase"):
        live = trace.live_spans()
    release.set()
    t.join(10)
    # the worker's stack was visible, outermost first, on its own
    # track; the main thread's on another
    worker_stacks = [v for k, v in live.items() if "obs-worker" in k]
    assert worker_stacks == [["worker.phase", "worker.subphase"]]
    main_stacks = [v for k, v in live.items() if "obs-worker" not in k]
    assert ["main.phase"] in main_stacks
    # recorded events carry distinct thread idents
    tids = {e[2] for e in trace.events()}
    assert len(tids) == 2
    assert trace.live_spans() == {}         # everything closed


def test_chrome_trace_json_validates(tmp_path):
    trace.enable()
    with trace.span("phase", args={"n": 3}):
        trace.instant("tick")
    trace.add_span("retro", 1.0, 1.5, tid=999, args={"id": "r0"})
    trace.set_track_name(999, "slot-lane")
    path = trace.dump_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        obj = json.load(f)
    _validate_chrome(obj)
    by_name = {e["name"]: e for e in obj["traceEvents"]}
    assert by_name["phase"]["args"] == {"n": 3}
    assert by_name["retro"]["ph"] == "X"
    assert abs(by_name["retro"]["dur"] - 0.5e6) < 1.0  # 0.5s in us
    lanes = [e for e in obj["traceEvents"]
             if e["ph"] == "M" and e["tid"] == 999]
    assert lanes and lanes[0]["args"]["name"] == "slot-lane"


def test_ring_capacity_bounds_memory():
    trace.enable(capacity=8)
    try:
        for i in range(100):
            trace.instant(f"e{i}")
        evs = trace.events()
        assert len(evs) == 8
        assert [e[1] for e in evs] == [f"e{i}" for i in range(92, 100)]
    finally:
        trace.enable(capacity=trace._DEFAULT_CAPACITY)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_histogram_bucket_math():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("t_s", edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    data = h.collect()
    # cumulative le-buckets: 1.0 lands in its edge bucket
    # (bisect_left), 100 overflows to +Inf
    assert data["buckets"] == [[1.0, 2], [2.0, 2], [4.0, 3],
                               [float("inf"), 4]]
    assert data["count"] == 4 and abs(data["sum"] - 104.5) < 1e-9
    # quantiles: interpolated inside the landing bucket, monotone,
    # +Inf clamps to the top edge
    assert h.quantile(0.5) == pytest.approx(1.0)
    assert h.quantile(0.99) == pytest.approx(4.0)
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.75, 0.9, 1.0)]
    assert qs == sorted(qs)
    assert obs_metrics.Histogram("e").quantile(0.5) == 0.0  # empty
    with pytest.raises(ValueError):
        obs_metrics.Histogram("bad", edges=(2.0, 1.0))


def test_registry_identity_and_kind_conflict():
    reg = obs_metrics.MetricsRegistry()
    c1 = reg.counter("steps_total", labels={"engine": "e0"})
    c2 = reg.counter("steps_total", labels={"engine": "e0"})
    c3 = reg.counter("steps_total", labels={"engine": "e1"})
    assert c1 is c2 and c1 is not c3          # keyed by (name, labels)
    with pytest.raises(TypeError):
        reg.gauge("steps_total", labels={"engine": "e0"})
    c1.inc()
    c1.inc(4)
    assert c1.collect() == 5.0 and c3.collect() == 0.0


def test_scrape_survives_failed_lazy_numpy_scalars_and_escaping():
    reg = obs_metrics.MetricsRegistry()

    class _Boom:
        """A lazy value whose device computation failed: float() is
        the device_get and it raises."""

        def __float__(self):
            raise RuntimeError("async XLA error")

    g = reg.gauge("bad_gauge")
    g.set(_Boom())
    assert g.collect() is None            # failed lazy scrapes absent
    assert g.materialize_errors == 1
    c = reg.counter("mixed_total")
    c.inc(_Boom())
    c.inc(np.int64(3))                    # numpy scalar: host path
    assert c.collect() == 3.0             # siblings of a bad lazy live
    assert c.materialize_errors == 1
    h = reg.histogram("mix_s", edges=(1.0,))
    h.observe(_Boom())
    h.observe(np.float32(0.5))
    d = h.collect()
    assert d["count"] == 1 and h.materialize_errors == 1
    # exposition must survive hostile label values
    reg.counter("esc_total", labels={"path": 'a"b\\c\n'}).inc()
    text = obs_export.to_prometheus_text(reg)
    assert 'path="a\\"b\\\\c\\n"' in text


def test_registry_edges_conflict_and_unregister():
    reg = obs_metrics.MetricsRegistry()
    h1 = reg.histogram("lat_s", edges=(1.0, 2.0))
    # edges=None means "accept whatever exists"; identical explicit
    # edges are fine; CONFLICTING explicit edges must raise, not
    # silently mis-bucket the second site's observations
    assert reg.histogram("lat_s") is h1
    assert reg.histogram("lat_s", edges=(1.0, 2.0)) is h1
    with pytest.raises(ValueError):
        reg.histogram("lat_s", edges=(0.5, 1.0))
    assert reg.unregister("lat_s") is True
    assert reg.unregister("lat_s") is False        # already gone
    h2 = reg.histogram("lat_s", edges=(0.5, 1.0))  # name is free again
    assert h2 is not h1 and h2.edges == (0.5, 1.0)


class _CountingLazy:
    """Stand-in for a LazyScalar: float() is the sync."""

    def __init__(self, v):
        self.v = v
        self.syncs = 0

    def __float__(self):
        self.syncs += 1
        return float(self.v)


def test_lazy_values_defer_sync_to_scrape():
    """The hot-path contract: instruments HOLD lazy device values;
    the D2H sync happens at scrape, and scrape(materialize=False)
    never syncs at all."""
    reg = obs_metrics.MetricsRegistry()
    g, c = reg.gauge("loss"), reg.counter("toks_total")
    h = reg.histogram("lat_s", edges=(1.0, 10.0))
    lg, lc, lh = _CountingLazy(2.5), _CountingLazy(3), _CountingLazy(0.5)
    g.set(lg)
    c.inc(lc)
    h.observe(lh)
    assert lg.syncs == lc.syncs == lh.syncs == 0      # recording: free
    snap = obs_export.snapshot(reg, materialize=False)
    assert lg.syncs == lc.syncs == lh.syncs == 0      # hungless scrape
    assert snap["loss"]["value"] is None
    assert snap["toks_total"]["value"] == 0.0
    assert snap["lat_s"]["count"] == 0
    snap = obs_export.snapshot(reg)                    # THE sync point
    assert lg.syncs == lc.syncs == lh.syncs == 1
    assert snap["loss"]["value"] == 2.5
    assert snap["toks_total"]["value"] == 3.0
    assert snap["lat_s"]["count"] == 1
    obs_export.snapshot(reg)
    assert lg.syncs == 1            # gauge caches its materialization


def test_real_lazyscalar_on_gauge():
    import jax.numpy as jnp
    from paddle_tpu.framework.lazy import LazyScalar
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("fit_loss").set(LazyScalar(jnp.float32(3.5)))
    assert obs_export.snapshot(reg)["fit_loss"]["value"] == 3.5


def test_function_gauge_and_dead_engine():
    reg = obs_metrics.MetricsRegistry()
    depth = [4]
    g = reg.gauge("queue_depth")
    g.set_function(lambda: depth[0])
    assert g.collect() == 4.0
    depth[0] = 7
    assert g.collect() == 7.0       # collect-time-computed, no staleness
    g.set_function(lambda: (_ for _ in ()).throw(RuntimeError()))
    assert g.collect() is None      # a dead backend scrapes as absent


def test_pending_lazy_values_are_bounded():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("x_s", edges=(1.0,))
    for i in range(obs_metrics._MAX_PENDING + 10):
        h.observe(_CountingLazy(0.5))
    assert h.pending_dropped == 10
    snap = obs_export.snapshot(reg)
    assert snap["x_s"]["count"] == obs_metrics._MAX_PENDING
    assert snap["x_s"]["pending_dropped"] == 10


def test_prometheus_text_format():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("steps_total", "steps", labels={"engine": "e0"}).inc(3)
    reg.gauge("depth").set(2)
    reg.histogram("lat_s", edges=(1.0, 2.0)).observe(1.5)
    text = obs_export.to_prometheus_text(reg)
    assert '# TYPE steps_total counter' in text
    assert 'steps_total{engine="e0"} 3' in text
    assert "depth 2" in text.splitlines()
    assert '# TYPE lat_s histogram' in text
    assert 'lat_s_bucket{le="1"} 0' in text
    assert 'lat_s_bucket{le="2"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text
    assert "lat_s_sum 1.5" in text and "lat_s_count 1" in text


# ---------------------------------------------------------------------------
# watchdog span dump
# ---------------------------------------------------------------------------
def test_watchdog_dumps_live_span_stack(tmp_path):
    """Regression (ISSUE 8 satellite): a wedged dispatch names the
    phase it wedged in — the watchdog dump carries the live span
    stack alongside the thread stacks."""
    from paddle_tpu.distributed.resilience.watchdog import HangWatchdog
    trace.enable()
    dump = tmp_path / "hang.txt"
    wd = HangWatchdog(timeout=3600, exit_code=None,
                      dump_path=str(dump))
    sp = trace.span("dispatch.group", args={"steps": 8})
    sp.__enter__()
    try:
        with trace.span("mesh.stage"):
            wd._dump(42.0)
    finally:
        sp.__exit__(None, None, None)
    text = dump.read_text()
    assert "live trace spans" in text
    assert "dispatch.group > mesh.stage" in text


def test_watchdog_dump_without_tracing_has_no_span_section(tmp_path):
    from paddle_tpu.distributed.resilience.watchdog import HangWatchdog
    assert not trace.enabled()
    dump = tmp_path / "hang.txt"
    wd = HangWatchdog(timeout=3600, exit_code=None,
                      dump_path=str(dump))
    wd._dump(42.0)
    assert "live trace spans" not in dump.read_text()


# ---------------------------------------------------------------------------
# profiler re-backing
# ---------------------------------------------------------------------------
def test_profiler_rebacked_on_unified_recorder(tmp_path, monkeypatch):
    """Profiler start/stop/export delegate to observability.trace:
    a profiled window's RecordEvent annotations land in the SAME
    timeline the framework instruments, and export_chrome_tracing
    dumps that unified trace."""
    import paddle_tpu.profiler as profiler
    monkeypatch.setenv("PADDLE_PROFILER_LOGDIR",
                       str(tmp_path / "xplane"))
    prof = profiler.Profiler(
        on_trace_ready=profiler.export_chrome_tracing(
            str(tmp_path), worker_name="w0"))
    assert not trace.enabled()
    prof.start()
    assert trace.enabled()          # start armed the recorder
    with profiler.RecordEvent("user_region"):
        with trace.span("framework.phase"):
            pass
    prof.step()
    prof.stop()
    assert not trace.enabled()      # stop disarmed what start armed
    with open(tmp_path / "w0.json") as f:
        obj = json.load(f)
    _validate_chrome(obj)
    names = {e["name"] for e in obj["traceEvents"]}
    # ONE timeline: the user annotation, the framework span and the
    # profiler's own step marker all in the same export
    assert {"user_region", "framework.phase",
            "profiler.step"} <= names


def test_profiler_start_respects_user_armed_recorder(tmp_path,
                                                     monkeypatch):
    import paddle_tpu.profiler as profiler
    monkeypatch.setenv("PADDLE_PROFILER_LOGDIR",
                       str(tmp_path / "xplane"))
    trace.enable()                  # user armed via PADDLE_TPU_TRACE
    prof = profiler.Profiler()
    prof.start()
    prof.stop()
    assert trace.enabled()          # stop must NOT disarm it


# ---------------------------------------------------------------------------
# instrumented stack: always-on metrics + merged timeline acceptance
# ---------------------------------------------------------------------------
def _tiny_fit_model():
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                        nn.Linear(32, 10))
    model = paddle.Model(net)
    model.prepare(optimizer.Adam(1e-3,
                                 parameters=model.parameters()),
                  nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    batches = [[rng.rand(16, 16).astype(np.float32),
                rng.randint(0, 10, (16,)).astype(np.int64)]
               for _ in range(8)]
    return model, net, batches


def test_fit_records_always_on_metrics_and_lazy_loss():
    """The dispatch engine + fit loop record counters/histograms and
    a LAZY loss gauge whether or not tracing is armed — and scrape is
    the only point that syncs it."""
    reg = obs_metrics.registry()
    c_steps = reg.counter("fit_steps_total")
    base = c_steps.collect()
    model, _net, batches = _tiny_fit_model()
    model.fit(batches, epochs=1, verbose=0, steps_per_dispatch=4)
    assert c_steps.collect() == base + len(batches)
    snap = paddle.observability.scrape()
    assert snap["dispatch_groups_total"]["value"] >= 2
    assert snap["dispatch_wall_s"]["count"] >= 2
    loss = snap["fit_loss"]["value"]
    assert loss is not None and np.isfinite(loss)


def test_merged_fit_serving_checkpoint_timeline(tmp_path):
    """THE acceptance pin (ISSUE 8): one fit(), one checkpoint save
    and one LLMServer session, traced together, export a single
    schema-valid Chrome trace; scrape() answers for all three
    subsystems from the same registry."""
    from paddle_tpu.distributed.checkpoint import CheckpointManager
    from paddle_tpu.inference.serving import LLMServer
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    trace.enable()
    # -- training ----------------------------------------------------
    model, net, batches = _tiny_fit_model()
    model.fit(batches, epochs=1, verbose=0, steps_per_dispatch=4)
    # -- checkpoint --------------------------------------------------
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert mgr.save(1, net, model._optimizer, force=True)
    mgr.wait_until_finished()
    mgr.close()
    # -- serving -----------------------------------------------------
    paddle.seed(0)
    gnet = GPTForCausalLM(gpt_tiny(use_flash_attention=False))
    gnet.eval()
    srv = LLMServer(gnet, max_batch=2, block_size=8, num_blocks=64,
                    auto_start=False)
    srv.start()
    try:
        futs = [srv.submit([1, 2, 3], 3),
                srv.submit([4, 5, 6, 7], 3)]
        res = [f.result(timeout=120) for f in futs]
        assert all(len(r.tokens) == 3 for r in res)
        st = srv.stats()
        # the public stats shape survives the registry re-backing and
        # reads back what the engine recorded
        assert st["completed"] == 2
        assert st["latency_p99_s"] >= st["latency_p50_s"] >= 0.0
        assert st["ttft_p99_s"] >= 0.0
        assert "fragmentation" in st["kv"]
    finally:
        srv.close()
    trace.disable()

    path = trace.dump_chrome_trace(str(tmp_path / "merged.json"))
    with open(path) as f:
        obj = json.load(f)
    _validate_chrome(obj)
    names = {e["name"] for e in obj["traceEvents"]}
    # all three subsystems on ONE timeline
    for want in ("fit", "fit.epoch", "fit.sync_boundary",
                 "dispatch.group", "checkpoint.save",
                 "serving.prefill", "serving.dispatch", "request",
                 "request.queued", "request.decode-groups"):
        assert want in names, f"missing span {want!r}"
    # serving request lanes carry Perfetto thread_name metadata
    lane_meta = [e for e in obj["traceEvents"] if e["ph"] == "M"
                 and e["args"]["name"].startswith("serving-")]
    assert lane_meta
    # ... and ONE registry answers for dispatch, serving, checkpoint
    snap = paddle.observability.scrape()
    joined = "\n".join(snap)
    for want in ("dispatch_steps_total", "fit_loss",
                 "serving_latency_s", "serving_tokens_total",
                 "checkpoint_saves_total", "checkpoint_save_s"):
        assert want in joined, f"missing metric {want!r}"
    # prometheus dump renders the same registry
    text = paddle.observability.scrape_prometheus()
    assert "# TYPE serving_latency_s histogram" in text
    assert "checkpoint_saves_total" in text
    # engine-churn hygiene: a retired engine's labeled children are
    # reclaimable, and only ITS labels disappear from the scrape
    eng_label = f'engine="{srv.engine._obs_id}"'
    assert eng_label in text
    srv.engine.unregister_metrics()
    after = paddle.observability.scrape_prometheus()
    assert eng_label not in after
    assert "checkpoint_saves_total" in after

# the static host-sync guard over observability/ now lives in
# tests/test_analysis.py (ISSUE 17: one parametrized module runs
# every pass on one shared parse)
