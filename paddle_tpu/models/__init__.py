"""paddle_tpu.models — flagship model families.

The reference ships model zoos via PaddleNLP/vision; in-tree it exercises Llama/GPT
through distributed tests (/root/reference/test/auto_parallel/hybrid_strategy/
semi_auto_llama.py:33, test/auto_parallel GPT tests). Here the model families are
first-class: mesh-aware (logical-axis sharding), remat-capable, jit-first.
"""

from . import afmoe  # noqa: F401
from . import bert  # noqa: F401
from . import gpt  # noqa: F401
from . import lfm2  # noqa: F401
from . import llama  # noqa: F401
from . import nemotron_h  # noqa: F401
from . import unet  # noqa: F401
from .afmoe import AfmoeConfig, AfmoeForCausalLM  # noqa: F401
from .bert import BertConfig, BertForMaskedLM, BertForSequenceClassification  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM  # noqa: F401
from .lfm2 import Lfm2Config, Lfm2ForCausalLM  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel  # noqa: F401
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM  # noqa: F401
from .unet import UNet2DConditionModel, UNetConfig  # noqa: F401
