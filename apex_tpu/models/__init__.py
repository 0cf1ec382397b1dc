from .resnet import (ResNet, BasicBlock, Bottleneck, resnet18, resnet34,
                     resnet50, resnet101)  # noqa: F401
from .bert import (BertForMaskedLM, BertLayer, BertModel, bert_base,
                   bert_large)  # noqa: F401
from .gpt import (  # noqa: F401
    GptBlock, GptModel, generate, gpt2_small, gpt2_medium,
    gpt2_large, gpt2_xl)
from .llama import (  # noqa: F401
    LlamaBlock, LlamaModel, llama_1b, llama_7b, llama_tiny)
from .latent_moe import (  # noqa: F401
    LatentAttention, LatentMoeBlock, LatentMoeModel, yarn_tables)
from .gqa_moe import (  # noqa: F401
    GqaAttention, GqaMoeBlock, GqaMoeModel)
from .hybrid_ssm_moe import (  # noqa: F401
    HybridAttnBlock, HybridExpertBlock, HybridMambaBlock, HybridSsmMoeModel,
    MambaMixer)
from .vit import VitBlock, VitModel, vit_base, vit_small  # noqa: F401
from .hf import (gpt2_from_hf, gpt2_to_hf_state_dict,  # noqa: F401
                 llama_from_hf, llama_to_hf_state_dict,
                 mixtral_from_hf, resnet_from_torch,
                 resnet18_from_torch, resnet50_from_torch)
from .seq2seq import (  # noqa: F401
    Seq2SeqDecoderLayer, TransformerSeq2Seq, seq2seq_generate,
    transformer_seq2seq)
