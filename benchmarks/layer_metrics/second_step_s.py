"""Layer: compile cache.  Host seconds of the second ``train_step``,
which builds a second executable today (PERF.md section 5)."""


def read(obs):
    return obs["setup"]["second_step_s"]
