"""Layer: model (``incubate/distributed/models/moe/grouped.py``).  The
fullest held expert's (token, expert) pairs over the mean of the held
experts', averaged over the layers, in the last step the program
observed: its gauges ``moe_expert_tokens_max{layer}`` and
``moe_expert_tokens_mean{layer}``, which ``train_solar_lm`` reads at the window's
end, as ``nh_moe_expert_imbalance`` reads them (its reader)."""

import os

from benchmarks.harness import report

read = report.load_reader(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "nh_moe_expert_imbalance")
