"""Layer: model (``models/gpt.py``).  Device milliseconds a step spent in
operations that are neither a Mosaic kernel nor a collective: XLA's own
fusions, matrix multiplications and copies, averaged over the devices."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace.steps:
        return None
    return 1e3 * trace.kind_seconds("other") / trace.steps
