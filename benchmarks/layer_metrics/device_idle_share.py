"""Layer: device.  Per cent of the traced window in which the device ran
no operation; on several chips, the idlest device."""


def read(obs):
    trace = obs["trace"]
    if trace is None:
        return None
    return 100.0 * trace.idle_share()
