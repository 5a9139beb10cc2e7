"""The host half from inside (PR 36), the benchmark's side: the reader of
the program's spans on a host plane written by hand, every number worked
out on paper; the eight metric files on no run, on a rehearsal's CPU
trace and on the hand-made one; and what ``BENCHMARK.json`` gained:
eight per-layer entries at its end and nothing else.

``hand_made_host_half.xspace.txt``, times in microseconds.  The window
is 0 to 100 (``bench.next_batch`` 0-2, ``bench.dispatch`` 2-22,
``next_batch`` 22-23, ``dispatch`` 23-47, ``bench.sync`` 53-100).  On the
thread ``python3``:

====================  ==========  ==========
span                  step 7      step 8
====================  ==========  ==========
``mesh.dispatch``     3-21        24-46
``mesh.stage``        4-8         25-27
``mesh.scalars``      8-10        27-31
``mesh.val_cache``    10-11       31-32
``mesh.launch``       11-17       32-42
``mesh.commit``       17-20       42-45
``host.gc``           18-19 (0)   —
launches              8.5 9.5 12  28 30 33
====================  ==========  ==========

and beside the steps a launch at 47.5 and ``host.gc`` 48-52 (generation
2); on the thread ``worker`` ``host.gc`` 60-61 (generation 1).  Device 0
runs instructions 9-10, 10.5-11.5, 12.5-40, 40.5-41.5, 42-48, 52-80 and
81-85 (68.5 busy, 31.5 idle) and seven programs: two conversions, the
step, two conversions, the step, ``jit_other``.  Device 1 runs 5-95: the
idlest is device 0.
"""

import importlib.util
import json
import os
import shutil
import sys

import pytest
from pytest import approx

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                       # noqa: E402
from benchmarks.harness import (cells, program_counters,      # noqa: E402
                                program_spans as ps, report, trace_reduce)

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
US = 1e-6
SPAN_METRICS = (
    "step_launch_ms_per_step", "step_host_own_ms_per_step",
    "idle_own_host_ms_per_step", "device_programs_per_step",
    "host_gc_ms_per_step")
COUNTER_METRICS = (
    "step_trace_lower_s", "step_compile_s", "step_programs_built")
NEW_METRICS = SPAN_METRICS + COUNTER_METRICS


def _hand_made():
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA,
                           "hand_made_host_half.xspace.txt")) as f:
        return ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))


@pytest.fixture(scope="module")
def half():
    said = []
    found = ps.summarize(_hand_made(), say=said.append)
    assert found is not None and not said
    return found


# --------------------------------------------------------------------------
# the hand-made host plane
# --------------------------------------------------------------------------
def test_a_steps_phases_by_hand(half):
    assert half.window == approx((0.0, 100 * US))
    assert half.thread == "python3" and half.step_ids == [7, 8]
    by_hand = [
        {"mesh.dispatch": 18, "mesh.stage": 4, "mesh.scalars": 2,
         "mesh.val_cache": 1, "mesh.launch": 6, "mesh.commit": 3,
         "own": 12, "bench.dispatch": 20},
        {"mesh.dispatch": 22, "mesh.stage": 2, "mesh.scalars": 4,
         "mesh.val_cache": 1, "mesh.launch": 10, "mesh.commit": 3,
         "own": 12, "bench.dispatch": 24}]
    for got, want in zip(half.steps, by_hand):
        assert got == approx({k: v * US for k, v in want.items()})
    # medians in milliseconds: launch (6 + 10) / 2, own 12 and 12
    assert half.median_ms(ps.LAUNCH) == approx(8e-3)
    assert half.median_ms(ps.OWN) == approx(12e-3)
    assert half.median_ms("mesh.nothing") is None


def test_the_collectors_pauses_of_every_thread(half):
    by_generation = {g: s for s, g in half.gc}
    assert by_generation == approx({0: 1 * US, 2: 4 * US, 1: 1 * US})
    assert half.gc_ms_per_step() == approx(6e-3 / 2)


def test_idle_by_the_phase_the_host_was_in_by_hand(half):
    # device 0's gaps: 0-9, 10-10.5, 11.5-12.5, 40-40.5, 41.5-42, 48-52,
    # 80-81, 85-100.  0-9 is 3 outside, 1 of mesh.dispatch's own lines,
    # 4 of stage and 1 of scalars; 10-10.5 lies in val_cache; the next
    # three in a launch; 48-52 is the collection between the steps; the
    # last two are outside
    by_hand = {"mesh.dispatch": 1, "mesh.stage": 4, "mesh.scalars": 1,
               "mesh.val_cache": 0.5, "mesh.launch": 2, "host.gc": 4,
               ps.OUTSIDE: 3 + 1 + 15}
    assert half.idle_device == 0
    assert half.idle == approx({k: v * US for k, v in by_hand.items()})
    assert sum(half.idle.values()) == approx(31.5 * US)
    # one partition of the same gaps the harness lays to its own spans
    harness = trace_reduce.summarize(_hand_made())
    assert sum(v for _, v in harness.top_idle_gaps(99)) == approx(
        sum(half.idle.values()))
    # inside a step and outside its launch: 1 + 4 + 1 + 0.5; the
    # collection between the steps is no step's
    assert half.idle_own_ms_per_step() == approx(6.5e-3 / 2)


def test_device_programs_a_step_joined_by_order(half):
    assert half.joined
    assert half.programs == approx(
        {"jit_convert_element_type": 2.0, "jit_step": 1.0})
    assert half.programs_outside == approx({"jit_other": 0.5})
    assert half.programs_per_step() == approx(3.0)


def test_the_table_says_all_of_it(half):
    text = "\n".join(half.lines(31.5 * US))
    for said in ("2 steps (step 7 to 8) on thread 'python3'",
                 "mesh.launch: 0.008, 0.008, 0.010",
                 "own: 0.012, 0.012, 0.012",
                 "3 collections, 0.006 ms in the window, the longest 0.004 "
                 "ms (generation 2)",
                 "outside the program 0.019, host.gc 0.004, mesh.stage "
                 "0.004, mesh.launch 0.002", "(the harness's idle_gaps: 0.03",
                 "jit_convert_element_type 2, jit_step 1; launched outside "
                 "it: jit_other 0.5"):
        assert said in text, said


def test_without_launch_events_every_run_of_the_window_counts(monkeypatch):
    monkeypatch.setattr(ps, "LAUNCHED", "an event this runtime lacks")
    found = ps.summarize(_hand_made(), say=lambda line: None)
    assert not found.joined and not found.programs_outside
    assert found.programs == approx({"jit_convert_element_type": 2.0,
                                     "jit_step": 1.0, "jit_other": 0.5})
    assert "not joined" in "\n".join(found.lines())


@pytest.mark.parametrize("spans,cut", [
    ([(0, 10, "a")], [(0, 10, "a")]),
    ([(0, 10, "a"), (2, 4, "b"), (4, 7, "c")],
     [(0, 2, "a"), (2, 4, "b"), (4, 7, "c"), (7, 10, "a")]),
    ([(0, 10, "a"), (2, 8, "b"), (3, 5, "c")],
     [(0, 2, "a"), (2, 3, "b"), (3, 5, "c"), (5, 8, "b"), (8, 10, "a")]),
    ([(0, 2, "a"), (5, 6, "b")], [(0, 2, "a"), (5, 6, "b")]),
])
def test_nested_spans_are_cut_into_one_partition(spans, cut):
    assert ps.innermost(spans) == cut


def test_a_trace_without_the_programs_spans_gives_nothing_and_says_so():
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA,
                           "hand_made_two_devices.xspace.txt")) as f:
        before = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    said = []
    assert ps.summarize(before, say=said.append) is None
    assert len(said) == 1 and "no mesh.dispatch span" in said[0]


# --------------------------------------------------------------------------
# the metric files
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_reader_returns_none_where_there_was_no_run(name):
    read = report.load_reader(ROOT, name)
    # as the other cells' tests ask it: no trace, no window, no set-up
    assert read({"trace": None, "chips": 1, "config": {}, "family": None,
                 "counters": {"before": {}, "after": {}}}) is None
    # an untraced run: a window, and no trace written since it started
    import time
    assert read({"trace": None, "chips": 1,
                 "window": {"start_s": time.perf_counter() + 3600.0}}
                ) is None


def test_the_readers_read_the_hand_made_run(half, capsys):
    obs = {"trace": None, "program_spans": half}
    got = {name: report.load_reader(ROOT, name)(obs)
           for name in SPAN_METRICS}
    assert got == approx({
        "step_launch_ms_per_step": 8e-3, "step_host_own_ms_per_step": 12e-3,
        "idle_own_host_ms_per_step": 3.25e-3,
        "device_programs_per_step": 3.0, "host_gc_ms_per_step": 3e-3})


def test_the_counter_readers_sum_the_series_they_name():
    found = {(("fun", "step"), ("phase", "trace")): 1.5,
             (("fun", "step"), ("phase", "lower")): 0.5,
             (("fun", "step"), ("phase", "backend_compile")): 7.0,
             (("fun", "other"), ("phase", "trace")): 100.0}
    assert program_counters.total(found, fun="step") == 9.0
    assert program_counters.total(found, fun="step", phase="lower") == 0.5
    assert program_counters.total(found, fun="none") == 0
    assert program_counters._by_phase(found, "step") == {
        "backend_compile": 7.0, "lower": 0.5, "trace": 1.5}
    obs = {"setup": {}, "program_counters": (
        {"trace": 1.5, "lower": 0.5, "backend_compile": 7.0}, {}),
        "step_programs": {"first": 1.0, "sharding": 1.0}}
    assert report.load_reader(ROOT, "step_trace_lower_s")(obs) == 2.0
    assert report.load_reader(ROOT, "step_compile_s")(obs) == 7.0
    assert report.load_reader(ROOT, "step_programs_built")(obs) == 2.0


def test_a_traced_rehearsal_reports_the_counters_and_the_cpus_launch(
        monkeypatch, capsys, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp_path, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = cells.load_cell("gpt2m-short-s128", str(tmp_path))
    obj = bench_run.run_cell(cell, report.RunOptions(
        seed=2_500_000_003, seconds=0.5, trace=True, rehearse=True))
    lines = capsys.readouterr().out.splitlines()
    metrics = {k: v["value"] for k, v in obj["metrics"].items()}
    assert obj["correct"]
    # the program's own counters, whatever the platform
    assert metrics["step_trace_lower_s"] > 0
    assert metrics["step_compile_s"] > 0
    assert metrics["step_programs_built"] >= 1
    # the CPU's trace has the host plane: the phases are read from it ...
    launch = metrics["step_launch_ms_per_step"]
    own = metrics["step_host_own_ms_per_step"]
    assert launch > 0 and own > 0
    assert metrics["host_gc_ms_per_step"] >= 0
    table = [line for line in lines if line.startswith("  mesh.")]
    assert [line.split(":")[0].strip() for line in table] == [
        "mesh.dispatch", *ps.PHASES]
    # ... and no device: nothing is laid to a device's gaps or programs
    assert "idle_own_host_ms_per_step" not in metrics
    assert "device_programs_per_step" not in metrics
    # the two clocks around one call agree: the harness's span holds the
    # program's, which is its launch and its own
    harness = next(line for line in lines
                   if line.startswith("  bench.dispatch:"))
    median = float(harness.split(":")[1].split(",")[0])
    assert launch + own <= median <= launch + own + 0.5
    assert any(line.startswith("the jitted step built an executable at "
                               "step 1: reason first") for line in lines)


# --------------------------------------------------------------------------
# what BENCHMARK.json gained, and the pin it trips on
# --------------------------------------------------------------------------
def test_the_benchmark_gained_eight_metrics_at_its_end_and_kept_the_rest(
        monkeypatch):
    """``test_nemotron_cell.py:178`` holds the benchmark less PR 34's
    entries to (4, 5, 4, 29) and holds that nothing stands after PR 34's
    entries, so it is expected to fail since this PR appended its eight
    (``tests/conftest.py`` says why it may not be edited here).  Nothing
    it holds is let go meanwhile: it fails as it stands, and its whole
    body, the Granite cell's pinned test inside it, runs here on
    ``BENCHMARK.json`` less this PR's eight."""
    bench = cells.load_benchmark(ROOT)
    had = dict(bench)
    had["per_layer"] = [m for m in bench["per_layer"]
                        if m["name"] not in NEW_METRICS]
    # appended: in the issue's order, after everything that was there
    assert bench["per_layer"][:len(had["per_layer"])] == had["per_layer"]
    assert tuple(m["name"] for m in bench["per_layer"][
        len(had["per_layer"]):]) == NEW_METRICS
    for m in bench["per_layer"][len(had["per_layer"]):]:
        span = m["name"] in SPAN_METRICS
        assert m == {
            "name": m["name"], "better": "lower",
            "unit": ("programs" if "programs" in m["name"] else
                     "ms" if span else "s"),
            "source": "program_span" if span else "program_counter",
            "layer": "step_engine" if span else "compile_cache",
            "moves": "tokens_per_s" if span else "setup_s"}
    # nothing else changed: what the file held, by count, and no entry
    # that was there names a new metric
    assert (len(had["configs"]), len(had["workloads"]),
            len(had["end_to_end"]), len(had["per_layer"])) == (5, 6, 4, 39)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert not [n for n in NEW_METRICS if n in json.dumps(had)]
    # every cell reports them: the runner is every cell's
    for w in bench["workloads"]:
        names = {m["name"] for m in cells.load_cell(w["name"], ROOT).per_layer}
        assert set(NEW_METRICS) <= names
    # readers == declared, as test_harness.py holds it
    readers = {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "benchmarks", "layer_metrics"))
        if f.endswith(".py")}
    assert readers == {m["name"] for m in bench["per_layer"]}

    spec = importlib.util.spec_from_file_location(
        "the_nemotron_cells_tests", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "test_nemotron_cell.py"))
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    pinned = (theirs.
              test_the_benchmark_gained_entries_at_the_end_and_kept_the_rest)
    with pytest.raises(AssertionError):     # eight entries after PR 34's
        pinned(monkeypatch)
    monkeypatch.setattr(cells, "load_benchmark", lambda root=ROOT: had)
    pinned(monkeypatch)
