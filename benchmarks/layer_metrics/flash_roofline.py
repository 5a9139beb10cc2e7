"""Layer: kernels.  The least time a chip could take for the attention
calls of one step (the larger of operations over peak FLOP/s and bytes
over peak bytes/s, from shapes, by the family's ``attention_step_cost``),
as per cent of the time the kernels took.  Nothing to read where no
kernel ran."""

from benchmarks.harness.cells import least_seconds


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace.steps:
        return None
    kernel_s = trace.kind_seconds("kernel") / trace.steps
    if kernel_s <= 0.0:
        return None
    cost = obs["family"].attention_step_cost(
        obs["config"], obs["traffic"]["batch"], obs["traffic"]["seq_len"])
    least, _ = least_seconds(cost["flops"] / obs["chips"],
                             cost["bytes"] / obs["chips"], obs["peaks"])
    return 100.0 * least / kernel_s
