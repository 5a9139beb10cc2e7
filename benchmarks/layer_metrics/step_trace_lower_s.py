"""Layer: compile cache.  Host seconds jax spent tracing the runner's
jitted step and lowering it to a module, over the whole process, by the
program's own counter ``jax_compile_seconds_total{fun="step",
phase="trace"|"lower"}`` (``harness/program_counters.py``): the part of
set-up no cache can take away."""

from benchmarks.harness import program_counters as pc


def read(obs):
    built = pc.step_compile(obs)
    if built is None:
        return None
    return built[0].get("trace", 0.0) + built[0].get("lower", 0.0)
