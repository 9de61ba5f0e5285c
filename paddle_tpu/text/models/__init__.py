from .gpt import (  # noqa: F401
    GPTBlock, GPTForPretraining, GPTLMHead, GPTModel, gpt_1p3b,
    gpt_pipeline_descs, gpt_tiny)
from .bert import (  # noqa: F401
    BertEmbeddings, BertEncoderLayer, BertForPretraining,
    BertForSequenceClassification, BertModel, BertPooler,
    BertPretrainingHeads, ErnieForPretraining, ErnieModel, bert_base,
    bert_large)
from .transformer import (  # noqa: F401
    InferTransformerModel, TransformerModel, position_encoding_init)
from .mixed_decoder import (  # noqa: F401
    GroupedQueryAttention, MixedDecoderBlock, MixedDecoderForBlockDiffusion,
    MixedDecoderForPretraining, MixedDecoderModel, MultiHeadLatentAttention,
    MultiTokenPrediction)
