"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  The
fullest held expert's (token, expert) pairs over the mean of the held
experts', averaged over the expert blocks, in the last step the program
observed: its gauges ``moe_expert_tokens_max{layer}`` and
``moe_expert_tokens_mean{layer}``, which the driver reads at the window's
end.  1 is a balanced router; the grouped products take as long as their
rows, so the step pays for the sum and a later exchange would pay for
the fullest."""


def read(obs):
    try:
        program = obs["counters"]["after"]["program"]
        most = program["moe_expert_tokens_max"]
        mean = program["moe_expert_tokens_mean"]
        if not most or not all(most) or not all(mean):
            return None
        return sum(a / b for a, b in zip(most, mean)) / len(most)
    except (KeyError, TypeError):       # a program without these gauges
        return None
