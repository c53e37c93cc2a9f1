"""Model zoo: runnable models exercising the framework end-to-end.

Analog of the reference's ``apex/transformer/testing/standalone_gpt.py`` /
``standalone_bert.py`` (single-file GPT/BERT driving the TP/PP stack) and
``examples/imagenet``'s torchvision ResNet-50.
"""

from apex_tpu.models.gpt import GPTConfig, GPTModel  # noqa: F401
from apex_tpu.models.bert import BertConfig, BertModel  # noqa: F401
from apex_tpu.models.hybrid_decoder import (  # noqa: F401
    HybridDecoderConfig,
    HybridDecoderModel,
)
from apex_tpu.models.resnet import ResNet50, ResNetConfig  # noqa: F401
from apex_tpu.models.t5 import (  # noqa: F401
    EncDecPipeline,
    EncoderDecoderModel,
    T5Config,
)
