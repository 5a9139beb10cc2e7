"""NLP model families (the PaddleNLP-layer models the baseline configs
name: GPT-3 for config 4, BERT/ERNIE for config 3 — BASELINE.json:9-10).

Built from fleet.meta_parallel layers so the same model runs serial
(single chip), tensor-parallel, and pipelined depending on the mesh.
"""

from .gpt import (  # noqa
    GPTConfig, GPTModel, GPTForCausalLM, GPTPretrainingCriterion,
    GPTForCausalLMPipe, gpt_tiny, gpt2_small, gpt3_1p3b)
from .bert import (  # noqa
    BertConfig, BertModel, BertForPretraining, BertPretrainingCriterion,
    BertForSequenceClassification, ErnieConfig, ErnieModel,
    ErnieForPretraining, ErniePretrainingCriterion,
    ErnieForSequenceClassification, bert_tiny, bert_base, ernie_3_base)
from .keye_lm import (  # noqa
    KeyeLMConfig, KeyeLMModel, KeyeLMForCausalLM,
    KeyeLMPretrainingCriterion, keye_lm_tiny)
from .granite_hybrid import (  # noqa
    GraniteHybridConfig, GraniteHybridModel, GraniteHybridForCausalLM,
    GraniteHybridPretrainingCriterion, granite_hybrid_tiny)
from .nemotron_h import (  # noqa
    NemotronHConfig, NemotronHModel, NemotronHForCausalLM,
    NemotronHPretrainingCriterion, nemotron_h_tiny)
from .sambay import (  # noqa
    SambaYConfig, SambaYModel, SambaYForCausalLM,
    SambaYPretrainingCriterion, sambay_tiny)
from .lfm2_moe import (  # noqa
    Lfm2MoeConfig, Lfm2MoeModel, Lfm2MoeForCausalLM,
    Lfm2MoePretrainingCriterion, lfm2_moe_tiny)
from .solar_open2 import (  # noqa
    SolarOpen2Config, SolarOpen2Model, SolarOpen2ForCausalLM,
    SolarOpen2PretrainingCriterion, solar_open2_tiny)
