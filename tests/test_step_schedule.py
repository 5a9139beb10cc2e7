"""What a step program asks of the compiler, and how its collectives
are read back (``distributed/step_schedule.py``).

The runner compiles its step with the TPU's collective scheduling
options on a mesh of more than one TPU device and with nothing
anywhere else; ``lower_step`` carries what the window runs; the
``step_collectives{kind, mode}`` reader counts a scheduled text's
collectives by how they run.  The platform is answered as TPU where a
case needs it: no chip is touched.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed import collective, step_schedule
from paddle_tpu.distributed.runner import DistributedRunner
from paddle_tpu.framework import dispatch
from paddle_tpu.observability import metrics

pytestmark = pytest.mark.dist


# -- which options ----------------------------------------------------------


def _mesh(n, **degrees):
    return collective.build_mesh(degrees or {"dp": n},
                                 devices=jax.devices()[:n])


@pytest.fixture
def as_tpu(monkeypatch):
    """The mesh's devices answer ``tpu`` to the platform question."""
    monkeypatch.setattr(step_schedule, "mesh_platform", lambda mesh: "tpu")


def test_one_device_mesh_asks_for_nothing(as_tpu):
    assert step_schedule.compiler_options(_mesh(1)) is None


def test_multi_device_tpu_mesh_gets_the_options(as_tpu):
    got = step_schedule.compiler_options(_mesh(4, dp=2, mp=2))
    assert got == step_schedule.COMPILER_OPTIONS
    assert got is not step_schedule.COMPILER_OPTIONS   # a copy


def test_multi_device_cpu_mesh_asks_for_nothing():
    mesh = _mesh(4, dp=2, mp=2)
    assert step_schedule.mesh_platform(mesh) == "cpu"
    assert step_schedule.compiler_options(mesh) is None


# -- what reaches jax.jit ---------------------------------------------------


@pytest.fixture
def jit_calls(monkeypatch):
    """Every ``jax.jit`` call's keyword arguments, the call itself
    passed through."""
    calls = []
    real = jax.jit

    def spy(fun, **kw):
        calls.append(kw)
        return real(fun, **kw)

    monkeypatch.setattr(jax, "jit", spy)
    return calls


def _runner(mesh):
    collective.set_mesh(mesh)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = optimizer.SGD(0.1, parameters=net.parameters())
    return DistributedRunner(net, opt, nn.MSELoss(), mesh=mesh)


def _batch():
    rng = np.random.RandomState(0)
    return ([rng.randn(8, 8).astype(np.float32)],
            [rng.randn(8, 4).astype(np.float32)])


@pytest.mark.parametrize("devices, fake_tpu", [(1, True), (4, False)])
def test_step_jit_call_is_the_parents_where_nothing_is_asked(
        jit_calls, monkeypatch, devices, fake_tpu):
    """A one-device mesh (even on a TPU) and a CPU mesh: the step's
    ``jax.jit`` gets ``donate_argnums`` and nothing else."""
    if fake_tpu:
        monkeypatch.setattr(step_schedule, "mesh_platform",
                            lambda mesh: "tpu")
    runner = _runner(_mesh(devices))
    runner.train_step(*_batch())
    assert jit_calls[0] == {"donate_argnums": (0, 3)}


def test_multi_device_tpu_step_and_lower_step_carry_the_options(
        jit_calls, as_tpu):
    """The step the window runs and what ``lower_step`` hands the
    benchmark are the same ``jax.jit`` with the options: compiled on
    the CPU, both are refused for the same TPU option."""
    runner = _runner(_mesh(4, dp=2, mp=2))
    batch = _batch()
    with pytest.raises(Exception, match="No such compile option") as run:
        runner.train_step(*batch)
    assert jit_calls[0] == {"donate_argnums": (0, 3),
                            "compiler_options":
                                step_schedule.COMPILER_OPTIONS}
    lowered = runner.lower_step(*batch)
    with pytest.raises(Exception, match="No such compile option") as low:
        lowered.compile()
    assert str(low.value) == str(run.value)


def test_folded_step_passes_the_options_on_and_nothing_else(jit_calls):
    def per_step(p, frozen, bufs, st, lr, key, md):
        return lr, (), p, st, {}

    dispatch.build_folded_step(per_step, 2)
    dispatch.build_folded_step(per_step, 2, compiler_options={"a": 1})
    assert "compiler_options" not in jit_calls[0]
    assert jit_calls[1]["compiler_options"] == {"a": 1}


def test_multi_device_fold_program_gets_the_options(jit_calls, as_tpu):
    runner = _runner(_mesh(4, dp=2, mp=2))
    runner.place()
    runner._build_fold(2, 1, ())
    assert jit_calls[-1]["compiler_options"] == \
        step_schedule.COMPILER_OPTIONS


# -- the gauge on a step the runner builds ---------------------------------


def _gauges():
    return {(dict(i.labels)["kind"], dict(i.labels)["mode"]): i.collect()
            for i in metrics.registry().instruments()
            if i.name == "step_collectives"}


def _compiles(fun="step", phase="backend_compile", name="events"):
    return sum(i.collect() for i in metrics.registry().instruments()
               if i.name == f"jax_compile_{name}_total"
               and dict(i.labels) == {"fun": fun, "phase": phase})


def _step_compiles():
    return _compiles()


def _gpt_runner():
    """A tiny GPT on a CPU dp2 x mp2 mesh, in a process whose jit
    dispatch cache has room: jax keeps the executables of at most 8192
    live jitted functions with explicit attributes in one shared cache,
    and a test worker that has run many tests can be at that limit.
    There a new jitted step keeps no executable, its ``_cache_size()``
    stays 0 and the runner never sees that it built a program."""
    jax.clear_caches()
    from paddle_tpu.models import (gpt_tiny, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    mesh = _mesh(4, dp=2, mp=2)
    collective.set_mesh(mesh)
    paddle.seed(0)
    net = GPTForCausalLM(gpt_tiny())
    opt = optimizer.AdamW(1e-3, parameters=net.parameters())
    ids = np.random.RandomState(0).randint(0, 256, (4, 32)).astype(np.int64)
    return DistributedRunner(net, opt, GPTPretrainingCriterion(),
                             mesh=mesh), ids


def test_multi_device_step_sets_the_gauge_without_compiling_again():
    """On a CPU dp2 x mp2 mesh: every kind and mode is set, the step's
    reductions are counted, and the step compiled once."""
    runner, ids = _gpt_runner()
    before = _step_compiles()
    runner.train_step([ids], [ids])
    assert _step_compiles() - before == 1
    got = _gauges()
    assert set(got) == {(k, m) for k in step_schedule.KINDS
                        for m in step_schedule.MODES}
    assert got[("all-reduce", "sync")] > 0


def test_reading_the_schedule_traces_and_lowers_nothing_again():
    """Building the step only points the gauge at it.  The first read
    lowers the step on the arguments of the call that built it and
    compiles that: jax answers both from the call's caches.  Its trace
    event finds the cached jaxpr (no time to speak of), and no lowering
    and no compile happen."""
    runner, ids = _gpt_runner()
    phases = ("trace", "lower", "backend_compile")
    before = {p: _compiles(phase=p) for p in phases}
    runner.train_step([ids], [ids])
    built = {p: _compiles(phase=p) - before[p] for p in phases}
    assert built == {"trace": 1, "lower": 1, "backend_compile": 1}
    trace_s = _compiles(phase="trace", name="seconds")
    assert _gauges()[("all-reduce", "sync")] > 0
    assert {p: _compiles(phase=p) - before[p] for p in phases} == \
        {"trace": 2, "lower": 1, "backend_compile": 1}
    assert _compiles(phase="trace", name="seconds") - trace_s < 0.05


def test_multi_device_fold_sets_the_gauge_without_compiling_again(
        monkeypatch):
    """The folded program reads its schedule as the step does: once for
    the executable its call built, compiled once."""
    runner, ids = _gpt_runner()
    read = []
    monkeypatch.setattr(step_schedule, "observe", read.append)
    before = _compiles(fun="program")
    runner.train_steps_folded([([ids], [ids]), ([ids], [ids])])
    assert _compiles(fun="program") - before == 1
    assert len(read) == 1
    assert read[0]()[("all-reduce", "sync")] > 0


def test_building_a_step_prints_nothing_until_the_gauge_is_read(
        monkeypatch):
    """The executable's text is printed when ``step_collectives`` is
    first collected, once however often it is read, and again only for
    the next step program the gauge is pointed at."""
    printed = []
    real = jax.stages.Compiled.as_text
    monkeypatch.setattr(jax.stages.Compiled, "as_text",
                        lambda self: printed.append(1) or real(self))
    runner, ids = _gpt_runner()
    calls = []
    point = runner._observe_schedule
    monkeypatch.setattr(runner, "_observe_schedule",
                        lambda fn, args: (calls.append((fn, args)),
                                          point(fn, args)))
    runner.train_step([ids], [ids])
    runner.train_step([ids], [ids])
    assert calls and printed == []
    first = _gauges()
    assert first == _gauges() and len(printed) == 1
    assert first[("all-reduce", "sync")] > 0
    point(*calls[0])
    assert _gauges() == first and len(printed) == 2


def test_gauge_falls_silent_with_its_runner():
    """The gauge reads the runner through a weak reference: once the
    runner is gone it is absent, and holds no executable alive."""
    import gc
    runner, ids = _gpt_runner()
    runner.train_step([ids], [ids])
    assert _gauges()[("all-reduce", "sync")] > 0
    del runner
    gc.collect()
    assert set(_gauges().values()) == {None}


def test_one_device_step_does_not_read_its_schedule(monkeypatch):
    read = []
    monkeypatch.setattr(step_schedule, "observe", read.append)
    _runner(_mesh(1)).train_step(*_batch())
    assert read == []


# -- the reader -------------------------------------------------------------

_HEAD = """HloModule step, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

%fused (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p)
}
"""


def _entry(*body):
    return _HEAD + "\nENTRY %main (x: f32[8], y: f32[8]) -> f32[8] {\n" \
        + "  %x = f32[8]{0} parameter(0)\n  %y = f32[8]{0} parameter(1)\n" \
        + "\n".join("  " + line for line in body) + "\n}\n"


_FUSION = ("%f = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop, "
           "calls=%fused")
_AR_START = ("%ars = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
             "channel_id=1, replica_groups={{0,1}}, to_apply=%add")
_AR_DONE = "%ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)"


@pytest.mark.parametrize("body, want", [
    pytest.param((_AR_START, _FUSION, _AR_DONE),
                 {("all-reduce", "overlapped"): 1}, id="pair-over-fusion"),
    pytest.param((_AR_START, _AR_DONE, _FUSION),
                 {("all-reduce", "async_idle"): 1}, id="pair-back-to-back"),
    pytest.param((_AR_START,
                  "%g = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %y, "
                  "f32[8]{0} %y)",
                  "%gte = f32[8]{0} get-tuple-element((f32[8]{0}, "
                  "f32[8]{0}) %g), index=0",
                  _AR_DONE),
                 {("all-reduce", "async_idle"): 1},
                 id="pair-over-bookkeeping-only"),
    pytest.param(("%ar = f32[8]{0} all-reduce(f32[8]{0} %x), channel_id=1, "
                  "replica_groups={{0,1}}, to_apply=%add", _FUSION),
                 {("all-reduce", "sync"): 1}, id="plain"),
    pytest.param(("%ar = f32[8]{0} all-reduce(f32[8]{0} %x), channel_id=1, "
                  "replica_groups={{0,1}}, to_apply=%add, frontend_attributes="
                  "{async_collective_name=\"all-reduce-start\"}", _FUSION),
                 {("all-reduce", "async_idle"): 1},
                 id="plain-made-from-an-idle-pair"),
    pytest.param(("%ags = (f32[8]{0}, f32[16]{0}) all-gather-start("
                  "f32[8]{0} %x), channel_id=2, replica_groups={{0,1}}, "
                  "dimensions={0}",
                  _FUSION,
                  "%agd = f32[16]{0} all-gather-done((f32[8]{0}, "
                  "f32[16]{0}) %ags)",
                  _AR_START, _AR_DONE),
                 {("all-gather", "overlapped"): 1,
                  ("all-reduce", "async_idle"): 1}, id="all-gather-pair"),
])
def test_reader_counts_by_kind_and_mode(body, want):
    assert dict(step_schedule.collective_modes(_entry(*body))) == want


_CHAIN = """
%start_c (p0: f32[8]) -> (f32[8], s32[2]) {
  %p0 = f32[8]{0} parameter(0)
  %ar.1 = f32[8]{0} all-reduce(f32[8]{0} %p0), channel_id=7, replica_groups={{0,2},{1,3}}, to_apply=%add, frontend_attributes={chain_id="1"}
  ROOT %cs = (f32[8]{0}, s32[2]{0}) custom-call(f32[8]{0} %ar.1), custom_call_target="AsyncCollectiveStart"
}

%step_c (q0: f32[8], q1: (f32[8], s32[2])) -> (f32[8], (f32[8], s32[2])) {
  %q0 = f32[8]{0} parameter(0)
  %q1 = (f32[8]{0}, s32[2]{0}) parameter(1)
  %mm = f32[8]{0} multiply(f32[8]{0} %q0, f32[8]{0} %q0)
  %ar.2 = f32[8]{0} all-reduce(f32[8]{0} %q0), channel_id=7, replica_groups={{0,2},{1,3}}, to_apply=%add, frontend_attributes={chain_id="1"}
  ROOT %tt = (f32[8]{0}, (f32[8]{0}, s32[2]{0})) tuple(f32[8]{0} %mm, (f32[8]{0}, s32[2]{0}) %q1)
}

%done_c (r0: (f32[8], s32[2])) -> f32[8] {
  %r0 = (f32[8]{0}, s32[2]{0}) parameter(0)
  %ar.3 = f32[8]{0} all-reduce(f32[8]{0} %r0), channel_id=7, replica_groups={{0,2},{1,3}}, to_apply=%add, frontend_attributes={chain_id="1"}
  ROOT %cd = f32[8]{0} custom-call((f32[8]{0}, s32[2]{0}) %r0, f32[8]{0} %ar.3), custom_call_target="AsyncCollectiveDone"
}
"""


def test_reader_follows_an_async_collective_fusion_across_its_steps():
    """The TPU's form: one all-reduce started in one fusion, stepped in
    a compute fusion and done in a third, all sharing its channel."""
    text = _entry(
        "%s = (f32[8]{0}, s32[2]{0}) fusion(f32[8]{0} %x), kind=kCustom, "
        "calls=%start_c",
        "%st = (f32[8]{0}, (f32[8]{0}, s32[2]{0})) fusion(f32[8]{0} %y, "
        "(f32[8]{0}, s32[2]{0}) %s), kind=kOutput, calls=%step_c",
        "%st1 = (f32[8]{0}, s32[2]{0}) get-tuple-element((f32[8]{0}, "
        "(f32[8]{0}, s32[2]{0})) %st), index=1",
        "%d = f32[8]{0} fusion((f32[8]{0}, s32[2]{0}) %st1), kind=kCustom, "
        "calls=%done_c")
    text = text.replace("\nENTRY", _CHAIN + "\nENTRY")
    assert dict(step_schedule.collective_modes(text)) == \
        {("all-reduce", "overlapped"): 1}


def test_reader_reads_a_loop_body_as_a_schedule_of_its_own():
    body = """
%body (c: f32[8]) -> f32[8] {
  %c = f32[8]{0} parameter(0)
  %ars = f32[8]{0} all-reduce-start(f32[8]{0} %c), channel_id=3, replica_groups={{0,1}}, to_apply=%add
  %f = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, calls=%fused
  ROOT %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)
}

%cond (k: f32[8]) -> pred[] {
  %k = f32[8]{0} parameter(0)
  ROOT %t = pred[] constant(true)
}
"""
    text = _entry("%w = f32[8]{0} while(f32[8]{0} %x), condition=%cond, "
                  "body=%body")
    text = text.replace("\nENTRY", body + "\nENTRY")
    assert dict(step_schedule.collective_modes(text)) == \
        {("all-reduce", "overlapped"): 1}


def test_reader_of_no_program_counts_nothing():
    assert not step_schedule.collective_modes("")


def test_observe_sets_every_kind_and_mode():
    text = _entry(_AR_START, _FUSION, _AR_DONE)
    step_schedule.observe(lambda: step_schedule.collective_modes(text))
    gauges = _gauges()
    assert gauges[("all-reduce", "overlapped")] == 1
    assert gauges[("all-gather", "sync")] == 0
