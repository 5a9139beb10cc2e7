"""Test config: force CPU backend with a virtual 8-device mesh so
distributed tests run without TPU hardware (SURVEY.md §4 "lessons":
single-host fakes of multi-node via xla_force_host_platform_device_count).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# Tests compile thousands of tiny per-shape XLA programs (deep zoo
# forwards alone hit ~500 compiles); LLVM optimization effort dominates
# wall time, not execution.  Drop to O0 for tests — semantics unchanged,
# execution of 64x64 shapes is negligible either way.
if "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0"
             " --xla_llvm_disable_expensive_passes=true").strip()
os.environ["XLA_FLAGS"] = flags
# keep synthetic datasets small in tests
os.environ.setdefault("PADDLE_TPU_SYNTH_N", "512")

# The tests are CPU-only whatever JAX_PLATFORMS says on this machine:
# force it via config before any computation runs.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# This jaxlib's CPU client races async-dispatched donated buffers
# against host reads under the 8-device virtual mesh: the suite
# intermittently segfaults/aborts inside compiled multi-device train
# steps (observed at different tests per run, always in XLA execution).
# Synchronous dispatch removes the race; on CPU tests the throughput
# difference is negligible.
jax.config.update("jax_cpu_enable_async_dispatch", False)

# Persistent compilation cache: repeat suite runs skip XLA compiles
# entirely (measured: densenet121 forward 15s cold -> 4.8s warm).
# framework/compile_cache.py decides where it lives: at
# JAX_COMPILATION_CACHE_DIR where that is set, else at the tests' own
# fixed, gitignored path; delete the dir to force cold compiles.
from paddle_tpu.framework import compile_cache as _compile_cache  # noqa: E402

_compile_cache.enable_compilation_cache(
    default_dir=os.path.join(os.path.dirname(__file__),
                             ".jax_compile_cache"))
# the tests' own thresholds (ROADMAP D0 looks here first)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Capability probe: can this jaxlib run MULTIPROCESS computations on
# the CPU backend?  Some container jaxlibs cannot ("Multiprocess
# computations aren't implemented on the CPU backend" — the known
# drift failures in ROADMAP): those tests then burn ~35 s of the
# tier-1 870 s wall clock per run failing identically.  The probe
# runs the minimal failing shape once (two children rendezvous and
# jit one cross-process sum) and CACHES the verdict per jax/jaxlib
# version, so every later suite run answers from disk in ~0 s; on a
# capable container the probe says yes once and the tests run
# normally forever after.
# ---------------------------------------------------------------------------
_MULTIPROC_PROBE_CACHE = os.path.join(
    os.path.dirname(__file__), ".multiproc_probe.json")

_MULTIPROC_PROBE_CHILD = """
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(sys.argv[1], num_processes=2,
                           process_id=int(sys.argv[2]))
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.array(jax.devices()), ("x",))
local = jax.device_put(np.array([1.0], np.float32),
                       jax.local_devices()[0])
arr = jax.make_array_from_single_device_arrays(
    (2,), NamedSharding(mesh, P("x")), [local])
total = float(jax.jit(jnp.sum,
                      out_shardings=NamedSharding(mesh, P()))(arr))
assert total == 2.0, total
print("PROBE-OK")
"""


def cpu_multiprocess_supported() -> bool:
    import json as _json
    import socket as _socket
    import subprocess as _sp
    import sys as _sys
    try:
        import jaxlib
        key = f"{jax.__version__}/{jaxlib.__version__}"
    except Exception:
        key = jax.__version__
    try:
        with open(_MULTIPROC_PROBE_CACHE) as f:
            d = _json.load(f)
        if d.get("key") == key:
            return bool(d["supported"])
    except Exception:
        pass
    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    procs = [_sp.Popen([_sys.executable, "-c",
                        _MULTIPROC_PROBE_CHILD, coord, str(r)],
                       env=env, stdout=_sp.PIPE, stderr=_sp.STDOUT,
                       text=True)
             for r in (0, 1)]
    supported = True
    saw_capability_error = False
    for p in procs:
        try:
            out, _ = p.communicate(timeout=90)
        except _sp.TimeoutExpired:
            p.kill()
            p.communicate()
            supported = False
            continue
        if p.returncode != 0 or "PROBE-OK" not in out:
            supported = False
            if "Multiprocess computations" in (out or ""):
                saw_capability_error = True
    # Cache positive verdicts always; cache a NEGATIVE verdict only
    # when the probe saw the actual capability error — a timeout or
    # crash on a loaded container must not permanently disable the
    # multiprocess coverage on a capable jaxlib (it just re-probes
    # next run).
    if supported or saw_capability_error:
        try:
            with open(_MULTIPROC_PROBE_CACHE, "w") as f:
                _json.dump({"key": key, "supported": supported}, f)
        except OSError:
            pass  # unwritable tree: probe again next run
    return supported


def require_cpu_multiprocess():
    """Shared skip guard for the cross-process rendezvous/training
    tests (test_spawn, test_launch_multiproc)."""
    if not cpu_multiprocess_supported():
        pytest.skip("this jaxlib cannot run multiprocess "
                    "computations on the CPU backend (cached "
                    "capability probe; ROADMAP container drift)")


@pytest.fixture
def retrace_strict():
    """Arm the runtime retrace sentinel for a test module
    (``pytestmark = pytest.mark.usefixtures("retrace_strict")``): any
    trace of a single-trace compiled entry after its first dispatch
    raises RetraceError instead of silently recompiling — the ambient
    form of the hand-written ``entries == 1, traces == 1`` pins."""
    from paddle_tpu.framework import dispatch as _dispatch
    _dispatch.set_retrace_strict(True)
    yield
    _dispatch.set_retrace_strict(None)


@pytest.fixture(autouse=True)
def _reset_state():
    """Isolate tests: fresh tape, fresh RNG, no leaked mesh."""
    import paddle_tpu as paddle
    from paddle_tpu.autograd import tape
    from paddle_tpu.distributed import collective
    tape.reset_tape()
    tape.set_grad_enabled(True)
    paddle.seed(12345)
    yield
    tape.reset_tape()
    tape.set_grad_enabled(True)
    collective.set_mesh(None)


# ``tests/benchmarks/test_granite_cell.py`` pins the number of cells the
# benchmark has (``len(bench["workloads"]) == 5``) inside its test of
# what its own cell declares.  A PR that adds a cell may edit no file the
# benchmark already has, a ``model_config`` PR has to add its cell, and
# tier-1 may not get worse: so that one test is expected to fail, on that
# line, from the sixth cell on (PR 34).  ``strict``: the day a
# ``benchmark`` PR takes the count out of it the test passes, this mark
# fails, and this hook goes (ROADMAP D16).  Until then
# ``test_nemotron_cell.py`` runs that test's whole body on the benchmark
# less what PR 34 appended
# (``test_the_benchmark_gained_entries_at_the_end_and_kept_the_rest``),
# so nothing the test held is let go.  (Not a ``conftest.py`` beside the
# test: ``tests/`` has no packages, so a second module named ``conftest``
# hides this one from ``from conftest import ...``.)
_PINNED_CELL_COUNT = (
    "test_granite_cell.py::"
    "test_the_cell_declares_its_metrics_and_reads_the_block_metrics")
# The test that stands in for it pins the benchmark in turn:
# ``test_nemotron_cell.py:178`` holds ``BENCHMARK.json`` less PR 34's
# entries to (4, 5, 4, 29) configurations, cells, end-to-end and
# per-layer metrics, and holds that nothing stands after PR 34's
# entries.  PR 36 (``tracing``) appends eight per-layer metrics of the
# host half, may edit no file under the benchmark's ``paths`` either,
# and takes the same way, openly: that test is expected to fail from the
# first entry after PR 34's on, and ``tests/benchmarks/test_host_half.py``
# sees it fail as it stands and runs its whole body, the Granite test's
# inside it, on the benchmark less PR 36's eight.  ROADMAP D16's
# ``benchmark`` PR deletes both pins and this hook.
_PINNED_SIZE = (
    "test_nemotron_cell.py::"
    "test_the_benchmark_gained_entries_at_the_end_and_kept_the_rest")
# PR 38 (``model_config``) appends a configuration, a cell and nine
# per-layer metrics, and the test that stood in for the second pin pins
# the benchmark in turn: ``test_host_half.py:279-306`` holds
# ``BENCHMARK.json`` less PR 36's eight to (5, 6, 4, 39) with nothing
# after those eight, and the metric directory's listing to what is
# declared.  Same way, openly, and now three deep:
# ``tests/benchmarks/test_sambay_cell.py`` sees it fail as it stands and
# runs its whole body, the two older pins inside it, on the benchmark
# less PR 38's entries and files.
_PINNED_HOST_HALF = (
    "test_host_half.py::"
    "test_the_benchmark_gained_eight_metrics_at_its_end_and_kept_the_rest")
# PR 40 (``model_config``) appends a configuration, a cell and eleven
# per-layer metrics, and the test that stood in for the third pin pins
# the benchmark in turn: ``test_sambay_cell.py:284-291`` takes what was
# there to be every entry that is not PR 38's, so an entry appended after
# PR 38's breaks it (its docstring's "the next PR appends without a fourth
# stand-in" did not hold).  Same way, openly, four deep:
# ``tests/benchmarks/test_lfm2_cell.py`` sees it fail as it stands and
# runs its whole body, the three older pins inside it, on the benchmark
# cut before PR 40's first entry by position, which no later append moves:
# it leaves no fifth.
_PINNED_SAMBAY = (
    "test_sambay_cell.py::"
    "test_the_benchmark_gained_entries_at_the_end_and_kept_the_rest")
_EXPECTED_TO_FAIL = {
    _PINNED_CELL_COUNT:
        "pins len(workloads) == 5; the benchmark has six cells since "
        "PR 34, which may not edit the file",
    _PINNED_SIZE:
        "pins the benchmark less PR 34's entries at 29 per-layer metrics "
        "with nothing after them; PR 36 appended eight and may not edit "
        "the file",
    _PINNED_HOST_HALF:
        "pins the benchmark less PR 36's eight at (5, 6, 4, 39) with "
        "nothing after them; PR 38 appended a configuration, a cell and "
        "nine metrics and may not edit the file",
    _PINNED_SAMBAY:
        "takes every entry that is not PR 38's to have been there before "
        "PR 38's; PR 40 appended a configuration, a cell and eleven metrics "
        "after them and may not edit the file",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for pinned, reason in _EXPECTED_TO_FAIL.items():
            if item.nodeid.endswith(pinned):
                item.add_marker(pytest.mark.xfail(
                    reason=reason, raises=AssertionError, strict=True))
