"""Model zoo.

Reference: the auto-parallel Llama fixture
(test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py) and
paddle.vision.models. The LLM families live here; vision models under
paddle_tpu.vision.models.
"""
from .generation import generate
from .llama import (
    LlamaConfig, LlamaForCausalLM, LlamaModel, LlamaDecoderLayer,
    LlamaAttention, LlamaMLP, llama_shard_plan,
)
from .bert import (
    BertConfig, BertModel, BertForPretraining,
    BertForSequenceClassification, BertEmbeddings, BertEncoderLayer,
    bert_shard_plan,
)
from .gpt import (
    GPTConfig, GPTModel, GPTForCausalLM, GPTDecoderLayer, gpt_shard_plan,
)
from .unet_diffusion import (
    DDPMScheduler, UNet2DConditionModel, UNetConfig,
)
from .ernie_moe import (
    ErnieMoeConfig, ErnieMoeForCausalLM, ErnieMoeModel, ernie_moe_shard_plan,
)
from .exaone_moe import (
    ExaoneMoeConfig, ExaoneMoeForCausalLM, ExaoneMoeModel,
)
