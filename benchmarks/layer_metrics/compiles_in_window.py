"""Layer: entry points and step engine.  Growth between the window's first
and last step of the two counts the program keeps of its own compiling:
the executables the jitted train step holds (``_step_fn._cache_size()``)
and ``dispatch_retraces_total``.  Should be 0.  What jax itself built or
loaded in the window, for any function, is said on an earlier line."""


def read(obs):
    before, after = obs["counters"]["before"], obs["counters"]["after"]
    return sum(after[k] - before[k] for k in ("step_programs", "retraces"))
