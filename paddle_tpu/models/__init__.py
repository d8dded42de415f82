"""Model zoo (flagships for the BASELINE.json configs: Llama for the 8B/70B
pretraining recipes, GPT/ERNIE-style encoder for NLP finetune, plus
paddle_tpu.vision models for the conv path)."""
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel, llama3_8b_config,
                    llama3_70b_config, llama_tiny_config)
from .gpt import GPTConfig, GPTForCausalLM, gpt2_small_config, gpt_tiny_config
from .ernie import ErnieConfig, ErnieForMaskedLM, ErnieForQuestionAnswering, \
    ErnieForSequenceClassification, ErnieForTokenClassification, ErnieModel, \
    ernie_tiny_config
from .phi4flash import (Phi4FlashConfig, Phi4FlashForCausalLM,
                        phi4flash_tiny_config)
from .mistral4 import (Mistral4Config, Mistral4ForCausalLM,
                       mistral4_tiny_config)
from .granitemoehybrid import (GraniteMoeHybridConfig,
                               GraniteMoeHybridForCausalLM,
                               granitemoehybrid_tiny_config)
from .afmoe import AfmoeConfig, AfmoeForCausalLM, afmoe_tiny_config

__all__ = [n for n in dir() if not n.startswith("_")]
