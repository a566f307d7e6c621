from .gate import (BaseGate, GShardGate, NaiveGate, SigmoidGate,  # noqa: F401
                   SwitchGate, topk_dispatch)
from .moe_layer import (DroplessMoE, ExpertFFN, MoELayer,  # noqa: F401
                        Relu2ExpertFFN, SwiGLUExpertFFN, dropless_ffn)
