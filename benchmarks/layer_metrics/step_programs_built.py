"""Layer: compile cache.  Executables the runner's jitted step built in
the process: ``mesh_step_programs_total`` over all its reasons.  1 is
what a step needs; each further one is a second compile or cache read
that set-up pays.  The lines of ``harness/program_counters.py`` say the
reason of each and the arguments that differed."""

from benchmarks.harness import program_counters as pc


def read(obs):
    reasons = pc.step_programs(obs)
    if not reasons:
        return None
    print("executables of the jitted step by reason: " + ", ".join(
        f"{k} {int(v)}" for k, v in sorted(reasons.items())), flush=True)
    return sum(reasons.values())
