"""Model zoo (reference: python/paddle/vision/models + the GPT fixtures the
reference uses for auto-parallel tests, test/auto_parallel/get_gpt_model.py).
These are the BASELINE.md ladder configs: LeNet, ResNet, BERT, GPT, LLaMA,
the Nemotron-H hybrid (Mamba-2 + attention + latent experts), the
K-EXAONE decoder (window + full attention, SwiGLU experts), the
DeepSeek-V3 decoder (latent attention, SwiGLU experts), the LFM2-MoE
decoder (gated short convolutions + grouped-query attention, SwiGLU experts;
trained, not served) and the SmallThinker decoder (window + rotary layers
beside full NoPE layers, a router that reads the attention's input, ReGLU
experts; trained, not served).
"""
from .lenet import LeNet
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, gpt2_small, gpt2_medium
from .bert import (BertConfig, BertForPretraining,
                   BertForSequenceClassification, BertModel)
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    llama_7b, llama_tiny, llama2_13b, llama2_70b)
from .dlrm import DLRM, DLRMConfig, dlrm_tiny
from .nemotron_h import (NemotronHConfig, NemotronHForCausalLM,
                         NemotronHModel, nemotron_h_tiny)
from .exaone_moe import (ExaoneMoeConfig, ExaoneMoeForCausalLM,
                         ExaoneMoeModel, exaone_moe_tiny)
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3ForCausalLM,
                          DeepseekV3Model, deepseek_v3_tiny)
from .olmo_hybrid import (OlmoHybridConfig, OlmoHybridForCausalLM,
                          OlmoHybridModel, olmo_hybrid_tiny)
from .lfm2_moe import (Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoeModel,
                       lfm2_moe_tiny)
from .solar_open2 import (SolarOpen2Config, SolarOpen2ForCausalLM,
                          SolarOpen2Model, solar_open2_tiny)
from .smallthinker import (SmallThinkerConfig, SmallThinkerForCausalLM,
                           SmallThinkerModel, smallthinker_tiny)

__all__ = [
    "LeNet", "GPTConfig", "GPTModel", "GPTForCausalLM",
    "DLRM", "DLRMConfig", "dlrm_tiny",
    "BertConfig", "BertModel", "BertForPretraining",
    "BertForSequenceClassification",
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "llama_7b", "llama_tiny", "llama2_13b", "llama2_70b",
    "gpt2_small", "gpt2_medium",
    "NemotronHConfig", "NemotronHModel", "NemotronHForCausalLM",
    "nemotron_h_tiny",
    "ExaoneMoeConfig", "ExaoneMoeModel", "ExaoneMoeForCausalLM",
    "exaone_moe_tiny",
    "DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM",
    "deepseek_v3_tiny",
    "OlmoHybridConfig", "OlmoHybridModel", "OlmoHybridForCausalLM",
    "olmo_hybrid_tiny",
    "Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM", "lfm2_moe_tiny",
    "SolarOpen2Config", "SolarOpen2Model", "SolarOpen2ForCausalLM",
    "solar_open2_tiny",
    "SmallThinkerConfig", "SmallThinkerModel", "SmallThinkerForCausalLM",
    "smallthinker_tiny",
]
