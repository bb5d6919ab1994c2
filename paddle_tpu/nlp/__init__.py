"""NLP model zoo (PaddleNLP parity subset).

ref: PaddleNLP paddlenlp/transformers/{gpt,bert,ernie}/modeling.py and
tokenizer_utils.py. TPU-native: every model is built from mesh-aware
layers (mpu Column/Row parallel linears, vocab-parallel embedding) so the
same module runs dense on one chip and tensor-parallel under a Mesh.
"""
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, GPTLMHeadModel,
    GPTPretrainingCriterion, GPT_CONFIGS,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining, BertPretrainingCriterion,
    BertForMaskedLM, BertForSequenceClassification,
    BertForTokenClassification, BertForQuestionAnswering, BERT_CONFIGS,
)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieModel, ErnieForPretraining, ErniePretrainingCriterion,
    ErnieForMaskedLM, ErnieForSequenceClassification,
    ErnieForTokenClassification, ErnieForQuestionAnswering, ERNIE_CONFIGS,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaModel, LlamaForCausalLM,
    LlamaPretrainingCriterion, LLAMA_CONFIGS,
)
from .axk1 import (  # noqa: F401
    AXK1Config, AXK1Model, AXK1ForCausalLM, AXK1_CONFIGS,
)
from .tokenizer import (  # noqa: F401
    BasicTokenizer, WordpieceTokenizer, BertTokenizer, GPTTokenizer,
)
from . import generation  # noqa: F401
# continuous-batching serving engine (paged KV cache); the Pallas
# paged kernels load lazily inside it, so this import stays light
from .serving import ServingEngine  # noqa: F401
