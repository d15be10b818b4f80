"""Flagship model families (the analogue of PaddleNLP's model zoo entries
named in BASELINE.md: Llama for LLM pretraining, plus GPT/ERNIE-style
encoder)."""

from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    LlamaPretrainingCriterion, llama_3_8b_config,
                    llama_3_70b_config, tiny_llama_config)
from .ernie import (ErnieConfig, ErnieModel, ErnieForSequenceClassification,
                    ErnieForTokenClassification, ErnieForQuestionAnswering,
                    ErnieForPretraining, ErniePretrainingCriterion,
                    ernie_base_config, tiny_ernie_config,
                    BertConfig, BertModel, BertForSequenceClassification,
                    BertForTokenClassification, BertForQuestionAnswering,
                    BertForPretraining)
from .gpt import (GPTConfig, GPTModel, GPTForCausalLM,
                  GPTPretrainingCriterion, gpt2_small_config,
                  gpt3_13b_config, tiny_gpt_config)
from .lfm2 import (Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoeModel,
                   tiny_lfm2_config)
from .kimi_linear import (KimiLinearConfig, KimiLinearForCausalLM,
                          KimiLinearModel, tiny_kimi_linear_config)
from .falcon_h1 import (FalconH1Config, FalconH1ForCausalLM, FalconH1Model,
                        tiny_falcon_h1_config)
from .longcat_flash import (LongCatFlashConfig, LongCatFlashForCausalLM,
                            LongCatFlashModel, tiny_longcat_flash_config)
from .ocr import (DBNet, DBNetConfig, DBLoss, DBFPN, DBHead, db_postprocess,
                  CRNN, CRNNConfig, CTCHeadLoss, ctc_greedy_decode,
                  PPOCRSystem)

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPretrainingCriterion", "llama_3_8b_config",
           "llama_3_70b_config", "tiny_llama_config",
           "ErnieConfig", "ErnieModel", "ErnieForSequenceClassification",
           "ErnieForTokenClassification", "ErnieForQuestionAnswering",
           "ErnieForPretraining", "ErniePretrainingCriterion",
           "ernie_base_config", "tiny_ernie_config",
           "BertConfig", "BertModel", "BertForSequenceClassification",
           "BertForTokenClassification", "BertForQuestionAnswering",
           "BertForPretraining",
           "GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPretrainingCriterion", "gpt2_small_config",
           "gpt3_13b_config", "tiny_gpt_config",
           "Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM",
           "tiny_lfm2_config",
           "KimiLinearConfig", "KimiLinearModel", "KimiLinearForCausalLM",
           "tiny_kimi_linear_config",
           "FalconH1Config", "FalconH1Model", "FalconH1ForCausalLM",
           "tiny_falcon_h1_config",
           "LongCatFlashConfig", "LongCatFlashModel", "LongCatFlashForCausalLM",
           "tiny_longcat_flash_config",
           "DBNet", "DBNetConfig", "DBLoss", "DBFPN", "DBHead",
           "db_postprocess", "CRNN", "CRNNConfig", "CTCHeadLoss",
           "ctc_greedy_decode", "PPOCRSystem"]
