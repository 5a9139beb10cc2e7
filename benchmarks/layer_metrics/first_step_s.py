"""Layer: compile cache (``framework/compile_cache.py``).  Host seconds
of the process's first ``train_step`` with its loss read: tracing,
lowering, and compiling or reading the persistent cache."""


def read(obs):
    return obs["setup"]["first_step_s"]
