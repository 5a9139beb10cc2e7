"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  The
fullest held expert's (token, expert) pairs over the mean of the held
experts', averaged over the layers, in the last step the program
observed: its gauges ``moe_expert_tokens_max{layer}`` and
``moe_expert_tokens_mean{layer}``.  1 is a balanced router; the grouped
products take as long as their rows, so the step pays for the sum and a
later exchange would pay for the fullest."""


def read(obs):
    try:
        from paddle_tpu.observability import metrics
        reg = metrics.registry()
        ratios = []
        for layer in range(int(obs["config"]["num_hidden_layers"])):
            labels = {"layer": str(layer)}
            most = reg.gauge("moe_expert_tokens_max", labels=labels).collect()
            mean = reg.gauge("moe_expert_tokens_mean",
                             labels=labels).collect()
            if not most or not mean:
                return None
            ratios.append(most / mean)
        return sum(ratios) / len(ratios) if ratios else None
    except Exception:       # a program without these gauges
        return None
