"""Cached-KV LLM serving end-to-end (the fused_multi_transformer role).

Flow: build a Llama -> greedy generate (ONE compiled dispatch for
prefill + the whole decode scan) -> LLMPredictor session (block decode,
K tokens per dispatch) -> save/load the serving artifact -> weight-only
int8.  Runs in seconds on CPU with the tiny config; `chip_smoke.py`
serves the 1.1B configuration through `ServingEngine` on a TPU chip.

Run: python examples/llama_serve.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference import LLMPredictor
from paddle_tpu.quantization import weight_only_quantize


def main():
    paddle.utils.enable_compile_cache()
    paddle.seed(0)
    cfg = models.tiny_llama_config()
    net = models.LlamaForCausalLM(cfg)
    net.eval()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (2, 8))

    # 1) model.generate: one compiled call, static KV cache
    toks = net.generate(paddle.to_tensor(prompt), max_new_tokens=12,
                        eos_token_id=None)
    print("generate:", np.asarray(toks._value)[0])

    # 2) serving session: prefill once, then decode incrementally in
    #    blocks (each block = one dispatch)
    pred = LLMPredictor(net, batch=2, prompt_len=8, max_cache_len=32,
                        steps_per_call=4)
    first = pred.start(prompt)
    more = pred.decode(11)
    session = np.concatenate([first[:, None], more], axis=1)
    print("session :", session[0])

    # 3) the artifact round-trip (StableHLO prefill + decode-block
    #    programs + weights; loads without the model class)
    with tempfile.TemporaryDirectory() as td:
        pred.save(td + "/llama_serve")
        loaded = LLMPredictor.load(td + "/llama_serve")
        again = loaded.generate(prompt, max_new_tokens=12)
    assert np.array_equal(again, session), "artifact must reproduce"
    print("artifact:", again[0], "(deterministic)")

    # 4) weight-only int8: halve the weight stream (decode is
    #    weight-streaming bound)
    qnet = weight_only_quantize(net, inplace=False,
                                skip=lambda name, l: name == "lm_head")
    qpred = LLMPredictor(qnet, batch=2, prompt_len=8, max_cache_len=32,
                         steps_per_call=4)
    print("int8    :", qpred.generate(prompt, max_new_tokens=12)[0])


if __name__ == "__main__":
    main()
