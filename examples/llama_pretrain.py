"""Llama pretraining — the flagship recipe (BASELINE.md configs 3/5).

Single chip:
    python examples/llama_pretrain.py --layers 4 --steps 20

Multi-device mesh (TP x DP x ZeRO; CPU simulation works too):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama_pretrain.py --dp 2 --mp 2 --sharding 2 \
        --layers 2 --hidden 64 --steps 5

The full training step (forward + loss + backward + AdamW + ZeRO layouts)
compiles into ONE XLA program; GSPMD shards it over the mesh from the
layer annotations.  Gradient merge: --accumulate N.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import time

import numpy as np


def main():
    paddle.utils.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--intermediate", type=int, default=8192)
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--sharding", type=int, default=1)
    ap.add_argument("--zero", choices=["os", "os_g", "p_g_os"], default=None)
    ap.add_argument("--accumulate", type=int, default=1)
    ap.add_argument("--bf16", action="store_true", default=True)
    ap.add_argument("--recompute", action="store_true", default=True)
    ap.add_argument("--save", type=str, default=None)
    args = ap.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    parallel = args.dp * args.mp * args.sharding > 1
    if parallel:
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet.fleet_base import (
            DistributedStrategy)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": args.dp, "mp_degree": args.mp, "pp_degree": 1,
            "sharding_degree": args.sharding, "sep_degree": 1,
        }
        fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        intermediate_size=args.intermediate, num_hidden_layers=args.layers,
        num_attention_heads=args.heads, num_key_value_heads=args.kv_heads,
        max_position_embeddings=args.seq, recompute=args.recompute,
        tensor_parallel=args.mp > 1)
    model = LlamaForCausalLM(cfg)
    model.train()
    if args.bf16:
        model.to(dtype="bfloat16")
    criterion = LlamaPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=args.lr, parameters=model.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(1.0),
        multi_precision=args.bf16)
    if args.zero:
        import paddle_tpu.distributed as dist
        model, opt, _ = dist.group_sharded_parallel(model, opt, args.zero)

    def loss_fn(net, tokens, labels):
        return criterion(net(tokens), labels)

    step = TrainStep(model, loss_fn, opt,
                     accumulate_steps=args.accumulate)

    n_params = sum(p.size for p in model.parameters())
    print(f"model: {n_params / 1e9:.2f}B params | "
          f"mesh dp={args.dp} mp={args.mp} sharding={args.sharding} | "
          f"b{args.batch} s{args.seq} accumulate={args.accumulate}")

    rng = np.random.default_rng(0)
    tokens = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size,
                     (args.batch, args.seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size,
                     (args.batch, args.seq)).astype(np.int32))

    loss = step(tokens, labels)
    print(f"step 0 (compile): loss {float(loss):.4f}")
    t0 = time.perf_counter()
    for i in range(1, args.steps + 1):
        loss = step(tokens, labels)
        if i % 10 == 0 or i == args.steps:
            dt = time.perf_counter() - t0
            tps = args.batch * args.seq * i / dt
            print(f"step {i}: loss {float(loss):.4f} "
                  f"({tps:,.0f} tokens/s)")
    if args.save:
        paddle.save(model.state_dict(), args.save + ".pdparams")
        paddle.save(opt.state_dict(), args.save + ".pdopt")
        print(f"saved checkpoint to {args.save}.pdparams/.pdopt")


if __name__ == "__main__":
    main()
