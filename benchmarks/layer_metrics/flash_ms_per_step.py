"""Layer: kernels (``ops/pallas_ops.py``).  Device milliseconds a step
spent in the Mosaic custom calls of the compiled step (the flash
attention forward, dq and dkv kernels), averaged over the devices.  Reads
0 where the program's own shape rule sends attention to its composed
form."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace.steps:
        return None
    return 1e3 * trace.kind_seconds("kernel") / trace.steps
