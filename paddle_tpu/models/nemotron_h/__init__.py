from .modeling import (  # noqa: F401
    NemotronHBlock,
    NemotronHConfig,
    NemotronHForCausalLM,
    NemotronHModel,
)
