"""Layer: compile cache.  Host seconds of the backend compiles of the
runner's jitted step, the persistent cache's reads included (a hit is a
compile that took its retrieval time), over the whole process, by
``jax_compile_seconds_total{fun="step", phase="backend_compile"}``.  The
line of ``harness/program_counters.py`` says the hits (``cache_retrieval``),
the misses and the retrieval seconds beside it."""

from benchmarks.harness import program_counters as pc


def read(obs):
    built = pc.step_compile(obs)
    return None if built is None else built[0].get("backend_compile", 0.0)
