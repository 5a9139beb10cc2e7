"""Layer: device.  GB (1e9 bytes) a device needs for the compiled train
step by XLA's own ``memory_analysis()``: arguments + temporaries +
outputs - aliased bytes.  Where ``peak_hbm_gb`` reads well above it, a
transient of set-up sets the peak and not the step."""


def read(obs):
    step_bytes = obs["compiled_step"]["step_bytes"]
    return None if step_bytes is None else step_bytes / 1e9
