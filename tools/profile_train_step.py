"""Profile the bench Llama train step: per-op device-time table from the
xplane trace (smaller config than the headline: the profiler needs HBM
headroom)."""
import sys
sys.path.insert(0, "/root/repo")
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.jit.train_step import TrainStep
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaPretrainingCriterion)

L = int(sys.argv[1]) if len(sys.argv) > 1 else 8
B = int(sys.argv[2]) if len(sys.argv) > 2 else 8
HEADLINE = len(sys.argv) > 3 and sys.argv[3] == "headline"
cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                  intermediate_size=8192, num_hidden_layers=L,
                  num_attention_heads=32, num_key_value_heads=8,
                  max_position_embeddings=2048, recompute=True,
                  # "headline" = remat dial + chunked fused lm_head+CE
                  # + bf16 moments
                  recompute_policy="save_attn_mlp" if HEADLINE else None,
                  recompute_policy_alt="save_attn" if HEADLINE else None,
                  recompute_policy_stride=2 if HEADLINE else 1,
                  fused_linear_loss=HEADLINE)
paddle.seed(0)
model = LlamaForCausalLM(cfg)
model.train()
model.to(dtype="bfloat16")
criterion = LlamaPretrainingCriterion(cfg)
opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=not HEADLINE)

if HEADLINE:
    def loss_fn(net, tokens, labels):
        return net(tokens, labels=labels)[0]
else:
    def loss_fn(net, tokens, labels):
        return criterion(net(tokens), labels)

step = TrainStep(model, loss_fn, opt)
rng = np.random.default_rng(0)
tokens = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (B, 2048)).astype(np.int32))
labels = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (B, 2048)).astype(np.int32))
float(step.run_steps(tokens, labels, steps=3))  # compile+warm

import jax
import tempfile
tdir = tempfile.mkdtemp(prefix="prof_train_")
jax.profiler.start_trace(tdir)
float(step.run_steps(tokens, labels, steps=3))
jax.profiler.stop_trace()

from paddle_tpu import profiler
rows = profiler.DeviceSummaryView(tdir).rows()
rows = [r for r in rows
        if not (r["name"].startswith("jit_") or r["name"].isdigit())]
total = sum(r["total_ms"] for r in rows)
print(f"config L={L} b={B}; total device ms over 3 steps: {total:.1f}")
for r in sorted(rows, key=lambda r: -r["total_ms"])[:60]:
    print(f'{r["total_ms"]:9.3f} ms  {100*r["total_ms"]/total:5.1f}%  '
          f'x{r["calls"]:<4} {r["name"][:84]}')
