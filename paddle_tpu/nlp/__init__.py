"""NLP model family (flagship models for BASELINE configs #3-#5).

The reference delegates these to PaddleNLP; they are part of the
capability surface (SURVEY.md §6: GPT tokens/sec is the headline metric),
so the TPU build ships them in-tree: GPT (decoder-only LM), BERT
(encoder), Llama (RMSNorm/RoPE/SwiGLU — exercises the new
ring-attention/sep axis).
"""
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM,  # noqa: F401
                  GPTForCausalLMPipe)
from .bert import BertConfig, BertModel  # noqa: F401
from .llama import LlamaConfig, LlamaModel, LlamaForCausalLM  # noqa: F401
from .laguna import (LagunaConfig, LagunaModel,  # noqa: F401
                     LagunaForCausalLM)
from .deepseek_v2 import (DeepseekV2Config, DeepseekV2Model,  # noqa: F401
                          DeepseekV2ForCausalLM)
from .keye_vl2 import (KeyeVL2Config, KeyeVL2Model,  # noqa: F401
                       KeyeVL2ForCausalLM)
from .mimo_v2 import (MiMoV2Config, MiMoV2Model,  # noqa: F401
                      MiMoV2ForCausalLM)
from .generation import (DecodeCache, init_decode_caches,  # noqa: F401
                         update_and_attend, CompiledGenerator)
