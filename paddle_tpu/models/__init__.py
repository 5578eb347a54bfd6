from paddle_tpu.models.bert import (
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
)
from paddle_tpu.models.albert import AlbertConfig, AlbertForMaskedLM
from paddle_tpu.models.bart import (BartConfig,
                                    BartForConditionalGeneration,
                                    MBartConfig,
                                    MBartForConditionalGeneration)
from paddle_tpu.models.big_bird import (BigBirdConfig, BigBirdForMaskedLM,
                                        BigBirdModel)
from paddle_tpu.models.bloom import BloomConfig, BloomForCausalLM
from paddle_tpu.models.clip import (CLIPConfig, CLIPModel, CLIPTextModel,
                                    CLIPVisionModel)
from paddle_tpu.models.deberta import (DebertaV2Config,
                                       DebertaV2ForMaskedLM, DebertaV2Model)
from paddle_tpu.models.distilbert import (DistilBertConfig,
                                          DistilBertForMaskedLM,
                                          DistilBertModel)
from paddle_tpu.models.electra import (ElectraConfig, ElectraForPreTraining,
                                       ElectraModel)
from paddle_tpu.models.bart import (PegasusConfig,
                                    PegasusForConditionalGeneration)
from paddle_tpu.models.ernie import (ErnieConfig, ErnieForMaskedLM,
                                     ErnieForSequenceClassification,
                                     ErnieModel)
from paddle_tpu.models.bart import (BlenderbotConfig,
                                    BlenderbotForConditionalGeneration)
from paddle_tpu.models.ernie_m import (ErnieMConfig,
                                       ErnieMForSequenceClassification,
                                       ErnieMModel)
from paddle_tpu.models.fnet import FNetConfig, FNetForMaskedLM, FNetModel
from paddle_tpu.models.roformer import (RoFormerConfig,
                                        RoFormerForMaskedLM, RoFormerModel)
from paddle_tpu.models.roberta import (RobertaConfig, RobertaForMaskedLM,
                                       RobertaForSequenceClassification,
                                       RobertaModel)
from paddle_tpu.models.falcon import FalconConfig, FalconForCausalLM
from paddle_tpu.models.gemma import GemmaConfig, GemmaForCausalLM
from paddle_tpu.models.gpt_neox import GPTNeoXConfig, GPTNeoXForCausalLM
from paddle_tpu.models.glm import GlmConfig, GlmForCausalLM
from paddle_tpu.models.gptj import (CodeGenConfig, CodeGenForCausalLM,
                                    GPTJConfig, GPTJForCausalLM)
from paddle_tpu.models.layoutlm import (LayoutLMConfig,
                                        LayoutLMForMaskedLM, LayoutLMModel)
from paddle_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from paddle_tpu.models.megatron_bert import (MegatronBertConfig,
                                             MegatronBertForMaskedLM,
                                             MegatronBertModel)
from paddle_tpu.models.mpnet import (MPNetConfig, MPNetForMaskedLM,
                                     MPNetModel)
from paddle_tpu.models.nezha import (NezhaConfig, NezhaForMaskedLM,
                                     NezhaModel)
from paddle_tpu.models.phi import PhiConfig, PhiForCausalLM
from paddle_tpu.models.qwen2_moe import Qwen2MoeConfig, Qwen2MoeForCausalLM
from paddle_tpu.models.whisper import (WhisperConfig,
                                       WhisperForConditionalGeneration)
from paddle_tpu.models.xlnet import (XLNetConfig, XLNetLMHeadModel,
                                     XLNetModel)
from paddle_tpu.models.opt import OPTConfig, OPTForCausalLM
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, LlamaModel
from paddle_tpu.models.moe_llm import MoEConfig, MoEForCausalLM
from paddle_tpu.models.resnet import (
    ResNet,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
)
from paddle_tpu.models.conformer import (ConformerConfig, ConformerEncoder,
                                         ConformerForCTC)
from paddle_tpu.models.mistral import MistralConfig, MistralForCausalLM, MistralModel
from paddle_tpu.models.qwen import Qwen2Config, Qwen2ForCausalLM, Qwen2Model
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM, OuroModel
from paddle_tpu.models.t5 import T5Config, T5ForConditionalGeneration
from paddle_tpu.models import convert
