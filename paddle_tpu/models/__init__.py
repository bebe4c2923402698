"""Model zoo (reference: python/paddle/vision/models + the GPT fixtures the
reference uses for auto-parallel tests, test/auto_parallel/get_gpt_model.py).
These are the BASELINE.md ladder configs: LeNet, ResNet, BERT, GPT, LLaMA,
and the Nemotron-H hybrid (Mamba-2 + attention + latent experts).
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
]
