"""Model zoo: language models (GPT, a latent-attention / routed-experts
decoder, a block-diffusion routed-experts decoder, a decoder of
sliding-window and full-attention layers, a decoder of short-convolution
and attention layers, BERT) + vision re-exports."""
from .gpt import (  # noqa: F401
    GPTModel, GPTBlock, GPTEmbeddings, GPTLMHead, GPTPretrainingCriterion,
    GPT_CONFIGS, gpt_pipe_model,
)
from .mla_moe import MLAMoEModel  # noqa: F401
from .sdar_moe import SDARMoEModel  # noqa: F401
from .afmoe import AfmoeModel  # noqa: F401
from .lfm2_moe import Lfm2MoeModel  # noqa: F401
from .programs import (  # noqa: F401
    KVRowSpec, ServedModel, ServingSpec, StepSpec)
from .bert import (  # noqa: F401
    BertModel, BertForSequenceClassification, BertForMaskedLM,
    BertPretrainingCriterion, BERT_CONFIGS,
)
from ..vision.models import (  # noqa: F401
    LeNet, resnet18, resnet50, vgg16, mobilenet_v2,
)
