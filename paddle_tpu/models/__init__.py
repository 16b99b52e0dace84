"""Flagship model family (BASELINE.json configs 3/4/5)."""
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTDecoderLayer,
    GPTForCausalLM,
    GPTModel,
)
from .latent_moe import (  # noqa: F401
    AfmoeConfig,
    AfmoeForCausalLM,
    LatentMoEConfig,
    LatentMoEForCausalLM,
    LatentMoEModel,
    MiMoV2Config,
    MiMoV2ForCausalLM,
)
from .generation import generate, sample_logits  # noqa: F401
from .trainer import (build_train_step, place_model,  # noqa: F401
                      prefetch_batches)
