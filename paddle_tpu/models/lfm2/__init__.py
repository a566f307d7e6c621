from .modeling import (  # noqa: F401
    Lfm2Config,
    Lfm2DecoderLayer,
    Lfm2ForCausalLM,
    Lfm2Model,
)
