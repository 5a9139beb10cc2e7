"""The host half from inside (PR 36), the program's side: every span is
a profiler annotation always and a ring record when armed; one
``train_step`` holds its five phases; what jax builds is counted where
it happens, by function; the runner counts its own executables and says
why each was built; the collector's pauses are a histogram and a span.
"""

import gc
import glob
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer, profiler
from paddle_tpu.distributed import collective
from paddle_tpu.distributed.runner import DistributedRunner
from paddle_tpu.observability import events as obs_events
from paddle_tpu.observability import host_events, metrics, trace

PHASES = ["mesh.stage", "mesh.scalars", "mesh.val_cache", "mesh.launch",
          "mesh.commit"]


@pytest.fixture(autouse=True)
def _disarmed():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()


def _toy_runner():
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    mesh = collective.build_mesh({"dp": 2}, devices=jax.devices()[:2])
    collective.set_mesh(mesh)
    return DistributedRunner(net, opt, nn.CrossEntropyLoss(), mesh=mesh)


def _batch(rows=8):
    rng = np.random.default_rng(0)
    return ([rng.random((rows, 8), dtype=np.float32)],
            [rng.integers(0, 4, (rows,)).astype(np.int64)])


def _counter(name, **labels):
    """The value of one series, 0.0 where it does not exist yet (read
    without creating it)."""
    want = tuple(sorted(labels.items()))
    for inst in metrics.registry().instruments():
        if inst.name == name and inst.labels == want:
            collected = inst.collect()
            return (collected["count"] if isinstance(collected, dict)
                    else collected)
    return 0.0


# --------------------------------------------------------------------------
# one recorder, one clock
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profiler session on the CPU: a span with the recorder off, one
    with it on, a ``RecordEvent``, a collection.  What the ring held
    after each, and the host plane's events by name."""
    from jax.profiler import ProfileData
    folder = str(tmp_path_factory.mktemp("host_half_trace"))
    trace.disable()
    trace.clear()
    ring = {}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(folder, profiler_options=options)
    try:
        with trace.span("host_half.off", {"k": 3}):
            pass
        ring["off"] = trace.events()
        trace.enable()
        with trace.span("host_half.on", {"k": 4}):
            pass
        ring["on"] = trace.events()
        trace.disable()
        with profiler.RecordEvent("host_half.record_event"):
            pass
        gc.collect()
    finally:
        jax.profiler.stop_trace()
        trace.clear()
    found = {}
    for path in glob.glob(os.path.join(folder, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("host"):
                        found.setdefault(ev.name, []).append(dict(ev.stats))
    return SimpleNamespace(ring=ring, found=found)


def test_a_span_is_in_the_profilers_trace_with_the_recorder_off(profiled):
    assert profiled.found["host_half.off"] == [{"k": 3}]
    assert profiled.ring["off"] == []       # and nothing in the ring


def test_a_span_is_in_the_profilers_trace_and_the_ring_when_armed(profiled):
    assert profiled.found["host_half.on"] == [{"k": 4}]
    (record,) = profiled.ring["on"]
    assert record[0] == "X" and record[1] == "host_half.on"
    assert record[5] == {"k": 4}


def test_record_event_leaves_one_annotation_not_two(profiled):
    assert len(profiled.found["host_half.record_event"]) == 1


def test_a_collection_is_a_span_of_the_profilers_trace(profiled):
    assert {"generation": 2} in profiled.found["host.gc"]


def test_a_retroactive_span_is_the_rings_alone():
    trace.enable()
    trace.add_span("host_half.retro", 1.0, 1.5)
    assert [e[1] for e in trace.events()] == ["host_half.retro"]
    assert "cannot be bridged" in trace.add_span.__doc__


# --------------------------------------------------------------------------
# train_step by phase
# --------------------------------------------------------------------------
def test_one_train_step_holds_the_five_phases_once_each_in_order():
    runner = _toy_runner()
    inputs, labels = _batch()
    runner.train_step(inputs, labels)          # builds: not looked at
    gc.disable()
    try:
        trace.enable()
        runner.train_step(inputs, labels)
        records = [e for e in trace.events() if e[1].startswith("mesh.")]
    finally:
        gc.enable()
    by_name = {e[1]: e for e in records}
    assert sorted(by_name) == sorted(PHASES + ["mesh.dispatch"])
    assert len(records) == 6                   # once each
    outer = by_name["mesh.dispatch"]
    assert outer[5] == {"step": 2} == {"step": runner._step_ctr}
    at = outer[3]
    for name in PHASES:                        # in order, one inside
        _, _, tid, start, duration, _ = by_name[name]
        assert tid == outer[2] and start >= at
        at = start + duration
    assert at <= outer[3] + outer[4]


def test_the_folded_entry_holds_the_same_five_names():
    runner = _toy_runner()
    inputs, labels = _batch()
    trace.enable()
    runner.train_steps_folded([(inputs, labels), (inputs, labels)])
    names = [e[1] for e in trace.events() if e[1].startswith("mesh.")]
    assert sorted(names) == sorted(PHASES + ["mesh.dispatch_folded"])
    assert names[-1] == "mesh.dispatch_folded"


# --------------------------------------------------------------------------
# set-up counted where it happens
# --------------------------------------------------------------------------
def _built(fun):
    return {phase: (_counter("jax_compile_events_total", phase=phase,
                             fun=fun),
                    _counter("jax_compile_seconds_total", phase=phase,
                             fun=fun))
            for phase in ("trace", "lower", "backend_compile")}


def test_a_fresh_jit_counts_once_under_other_and_a_second_call_not():
    x = jnp.ones((3, 5))
    before = _built("other")
    fresh = jax.jit(lambda a: jax.lax.add(a, a))
    fresh(x)
    after = _built("other")
    for phase in before:
        assert after[phase][0] == before[phase][0] + 1, phase
        assert after[phase][1] > before[phase][1], phase
    fresh(x)
    assert _built("other") == after


def test_a_registered_function_counts_under_its_name_and_lands_in_the_ring():
    def host_half_probe(a):
        return jax.lax.mul(a, a)

    host_events.register_fun("host_half_probe")
    x = jnp.ones((2, 7))               # builds a program of its own
    others = _built("other")
    trace.enable()
    t0 = time.monotonic_ns()
    jax.jit(host_half_probe)(x)
    t1 = time.monotonic_ns()
    assert {p: n for p, (n, _) in _built("host_half_probe").items()} == {
        "trace": 1, "lower": 1, "backend_compile": 1}
    assert _built("other") == others
    spans = {e[1]: e for e in trace.events() if e[1].startswith("jax.")}
    assert set(spans) == {"jax.trace:host_half_probe",
                          "jax.lower:host_half_probe",
                          "jax.backend_compile:host_half_probe"}
    for _, _, _, start, duration, _ in spans.values():
        # jax's wall clock laid onto the ring's monotonic one
        assert t0 - 5e6 <= start and start + duration <= t1 + 5e6


def test_what_the_cache_says_waits_for_the_compile_that_names_it():
    def series(phase, what="events"):
        return _counter(f"jax_compile_{what}_total", phase=phase, fun="step")

    before = {k: series(*k) for k in [
        ("cache_retrieval",), ("cache_retrieval", "seconds"),
        ("cache_miss",), ("backend_compile", "seconds")]}
    host_events._on_event("/jax/compilation_cache/cache_hits")
    host_events._on_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    host_events._on_event("/jax/compilation_cache/cache_misses")
    # nothing yet: the cache's events carry no function's name
    assert {k: series(*k) for k in before} == before
    host_events._on_time_span(
        "/jax/core/compile/backend_compile_duration", 10.0, 12.0,
        fun_name="jit(step)")
    assert series("cache_retrieval") == before[("cache_retrieval",)] + 1
    assert series("cache_retrieval", "seconds") == pytest.approx(
        before[("cache_retrieval", "seconds")] + 0.25)
    assert series("cache_miss") == before[("cache_miss",)] + 1
    assert series("backend_compile", "seconds") == pytest.approx(
        before[("backend_compile", "seconds")] + 2.0)
    # and once: the next compile finds nothing waiting
    host_events._on_time_span(
        "/jax/core/compile/backend_compile_duration", 12.0, 13.0,
        fun_name="jit(step)")
    assert series("cache_miss") == before[("cache_miss",)] + 1


# --------------------------------------------------------------------------
# the runner counts its own executables
# --------------------------------------------------------------------------
def _programs():
    return {reason: _counter("mesh_step_programs_total", reason=reason)
            for reason in ("first", *host_events.REASONS, "unknown")}


def test_the_runner_counts_its_executables_with_their_reasons():
    runner = _toy_runner()
    inputs, labels = _batch()
    before = _programs()
    runner.train_step(inputs, labels)
    runner.train_step(inputs, labels)
    grew = {k: v - before[k] for k, v in _programs().items() if v != before[k]}
    assert grew.pop("first") == 1
    # a second executable, where the second call's arguments differ from
    # the first's (a chip's do), has a reason of its own
    assert sum(grew.values()) == runner._step_fn._cache_size() - 1
    after = _programs()
    runner.train_step(inputs, labels)          # builds nothing: counts none
    assert _programs() == after
    runner.train_step(*_batch(rows=16))        # another batch: another
    now = _programs()
    assert now["shape"] == after["shape"] + 1
    assert sum(now.values()) == sum(after.values()) + 1
    said = [e for e in obs_events.snapshot() if e["kind"] == "step_program"]
    assert said[-1]["reason"] == "shape" and said[-1]["step"] == 4
    assert any("(8, 8) -> (16, 8)" in d for d in said[-1]["differing"])


def _leaf(shape=(4, 2), dtype="float32", weak_type=False, sharding="s0",
          committed=True, layout="l0"):
    return SimpleNamespace(shape=shape, dtype=dtype, weak_type=weak_type,
                           sharding=sharding, committed=committed,
                           format=SimpleNamespace(layout=layout))


@pytest.mark.parametrize("reason,changed", [
    ("shape", dict(shape=(8, 2))),
    ("dtype", dict(dtype="bfloat16")),
    ("weak_type", dict(weak_type=True)),
    ("sharding", dict(sharding="s1")),
    ("committed", dict(committed=False)),
    ("layout", dict(layout="l1")),
    ("unknown", dict()),
    ("unknown", dict(layout=None)),       # donated: no longer known
    ("shape", dict(shape=(8, 2), sharding="s1")),    # the first that differs
])
def test_the_reason_is_the_first_thing_that_differs(reason, changed):
    before = host_events.argument_signature(({"w": _leaf(), "b": _leaf()},))
    now = host_events.argument_signature(
        ({"w": _leaf(**changed), "b": _leaf()},))
    got, differing = host_events.signature_change(before, now)
    assert got == reason
    assert len(differing) == (0 if reason == "unknown" else 1)
    if differing:
        assert differing[0].startswith("[0]['w']: ")


def test_no_executable_before_is_first_and_a_leaf_that_came_is_shape():
    one = host_events.argument_signature(({"w": _leaf()},))
    two = host_events.argument_signature(({"w": _leaf(), "b": _leaf()},))
    assert host_events.signature_change(None, one) == ("first", [])
    assert host_events.signature_change(one, two) == (
        "shape", ["[0]['b']: new"])
    many = {f"p{i}": _leaf() for i in range(9)}
    moved = {k: _leaf(sharding="s1") for k in many}
    reason, differing = host_events.signature_change(
        host_events.argument_signature((many,)),
        host_events.argument_signature((moved,)))
    assert reason == "sharding" and differing[-1] == "and 5 more"


# --------------------------------------------------------------------------
# the collector's pauses
# --------------------------------------------------------------------------
def test_a_collection_adds_one_pause_of_its_generation():
    gc.disable()                       # none but the one asked for
    try:
        trace.enable()
        before = _counter("host_gc_pause_s", generation="2")
        gc.collect()
        assert _counter("host_gc_pause_s", generation="2") == before + 1
        spans = [e for e in trace.events() if e[1] == "host.gc"]
    finally:
        gc.enable()
    assert len(spans) == 1 and spans[0][5] == {"generation": 2}
    assert host_events._on_gc in gc.callbacks
    assert gc.callbacks.count(host_events._on_gc) == 1
    host_events.install()              # once a process
    assert gc.callbacks.count(host_events._on_gc) == 1
