"""Layer: sharding.  Per cent of the traced window in which a device ran
a collective and no other operation, averaged over the devices: the
communication that compute does not hide."""


def read(obs):
    trace = obs["trace"]
    if trace is None:
        return None
    return 100.0 * trace.exposed_collective_seconds() / trace.window_s
