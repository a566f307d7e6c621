from .modeling import (  # noqa: F401
    AfmoeAttention,
    AfmoeConfig,
    AfmoeDecoderLayer,
    AfmoeForCausalLM,
    AfmoeModel,
)
