"""Training: ``TrainStep`` on a fresh seeded batch every step, fed through the
program's ``DataLoader`` from an in-memory seeded dataset; under ``fleet`` where
the mix names a layout.

Set-up builds one object, the compiled step with its state, drives it through
its first steps with the window's own call and feed, and hands the same object
to the window.  The reference follows those first steps afterwards.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from ..harness import compare, serving, traffic, weights


class _Rows:
    """In-memory dataset: row i is (tokens, labels) of one sequence."""

    def __init__(self, rows):
        self.rows = rows

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.rows[i, :-1], self.rows[i, 1:]


def _leaf_norms(arrays):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                             for x in xs])
    return [float(v) for v in fn(list(arrays))]


def _change_norms(cell, seed, params):
    """Per leaf, the norm of what the parameters moved by since the seed's
    values, which are made again rather than kept."""
    import jax
    import jax.numpy as jnp
    cfg = cell.config["model"]
    values = [p._value for p in params]
    start = weights.make(cell.family, cfg, seed, cfg["dtype"],
                         shardings=serving.param_shardings(values))
    fn = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))) for x, y in zip(a, b)])
    return [float(v) for v in fn(values, start)]


def build(ctx):
    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader
    from paddle_tpu.jit.train_step import TrainStep
    cell = ctx.cell
    cfg, mix = cell.config["model"], cell.traffic
    training = cell.config["training"]
    hyper = training["optimizer"]
    layout = mix.get("fleet")
    if layout:
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet.fleet_base import DistributedStrategy
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": layout["dp"], "mp_degree": layout["mp"],
            "pp_degree": 1, "sharding_degree": 1, "sep_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
    paddle.set_flags({"FLAGS_use_fused_adamw_kernel":
                      bool(training.get("fused_adamw_kernel"))})
    model = serving.build_model(
        cell, ctx.seed, ctx.phases, train=True,
        tensor_parallel=bool(layout and layout["mp"] > 1),
        **mix.get("model_options", {}))
    opt = paddle.optimizer.AdamW(
        learning_rate=hyper["lr"], beta1=hyper["beta1"], beta2=hyper["beta2"],
        epsilon=hyper["epsilon"], weight_decay=hyper["weight_decay"],
        parameters=model.parameters(),
        multi_precision=bool(training["multi_precision"]))
    step = TrainStep(model, cell.family.train_loss(model), opt)
    rows = traffic.token_rows(mix["dataset_batches"] * mix["batch"], mix["seq"],
                              cell.family.vocab_size(cfg), ctx.seed)
    loader = DataLoader(_Rows(rows), batch_size=mix["batch"], shuffle=False,
                        drop_last=True, num_workers=0)
    ctx.phases.mark("step_object")
    return model, step, loader, rows


def first_steps(ctx, model, step, feed, n_follow):
    """The first steps, through the window's own call and feed; returns the
    program's readings for the comparison."""
    hyper = ctx.cell.config["training"]["optimizer"]
    losses, grad_norms = [], None
    for i in range(n_follow):
        tokens, labels = next(feed)
        losses.append(float(step(tokens, labels)))
        if i == 0:
            ctx.phases.mark("first_step")
            m_norms = _leaf_norms([s["m"] for s in step._state])
            grad_norms = [v / (1.0 - hyper["beta1"]) for v in m_norms]
    change = _change_norms(ctx.cell, ctx.seed, step._params)
    ctx.phases.mark("followed_steps")
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


def _cycle(loader):
    while True:
        yield from loader


def run(ctx, control=None):
    cell = ctx.cell
    cfg, mix = cell.config["model"], cell.traffic
    chk = cell.config["check"]["train"]
    n_follow = int(chk["steps"])
    model, step, loader, rows = build(ctx)
    feed = _cycle(loader)
    program = first_steps(ctx, model, step, feed, n_follow)
    batch_tokens = mix["batch"] * mix["seq"]
    t0 = ctx.open_window()
    steps, wait_s, pending, trace = 0, 0.0, None, None
    tracing = False
    trace_steps = 0
    while True:
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
        if ctx.trace_on and not tracing and elapsed >= ctx.seconds - ctx.trace_seconds():
            if pending is not None:
                pending._value.block_until_ready()
            ctx.trace_begin()
            tracing, trace_steps = True, steps
        tw = time.perf_counter()
        with ctx.annotate("bench.loader"):
            tokens, labels = next(feed)
        wait_s += time.perf_counter() - tw
        with ctx.annotate("bench.train_step"):
            loss = step(tokens, labels)
        with ctx.annotate("bench.wait_previous"):
            if pending is not None:
                pending._value.block_until_ready()
        pending = loss
        steps += 1
    pending._value.block_until_ready()
    t_end = time.perf_counter()
    if tracing:
        trace_steps = steps - trace_steps
        trace = ctx.trace_end()
    final_loss = float(pending)
    from ..harness.context import device_info
    obs = {"window_s": t_end - t0, "steps": steps,
           "train_tok_s": steps * batch_tokens / (t_end - t0),
           "loader_wait_ms_per_step": 1e3 * wait_s / max(steps, 1),
           "compiles_in_window": ctx.compiles_in_window(),
           "flops_per_token": cell.family.train_flops_per_token(
               cfg, mix["seq"]),
           "attention_flops_per_step": cell.family.attention_train_flops(
               cfg, mix["batch"], mix["seq"]),
           "attempted": steps, "failed": 0 if np.isfinite(final_loss) else steps,
           "device": device_info(cell.chips), "trace": trace,
           "traced": {"steps": trace_steps} if trace is not None else None}
    print(f"window {obs['window_s']:.3f}s steps={steps} final_loss={final_loss:.4f} "
          f"first_losses={program['losses']}", flush=True)
    del model, step, loader, feed, pending, loss
    gc.collect()
    rows_, ok, got = check(ctx, program, rows, control)
    obs["check"] = got
    return obs, rows_, ok


def check(ctx, program, rows, control=None):
    """Follow the same first steps with the reference and compare.  ``control``
    names modes of the reference to read in the program's place: ``int8``,
    ``fp8`` (the control) and ``half`` (a fault: half of the batch left out)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cell = ctx.cell
    cfg, mix = cell.config["model"], cell.traffic
    chk = cell.config["check"]["train"]
    hyper = cell.config["training"]["optimizer"]
    ref = cell.reference
    n_follow, b = int(chk["steps"]), mix["batch"]
    devs = jax.devices()[:cell.chips]
    t = time.perf_counter()
    if len(devs) > 1:
        mesh = Mesh(np.array(devs), ("rows",))
        rep = NamedSharding(mesh, P())

        def shard(shape):
            return (NamedSharding(mesh, P("rows")) if len(shape) == 2
                    and shape[0] % len(devs) == 0 else rep)

        def put_rows(a):
            return jax.device_put(a, shard(a.shape) if a.shape[0] % len(devs) == 0
                                  else rep)
    else:
        shard, put_rows = None, None
    batches = [(rows[i * b:(i + 1) * b, :-1], rows[i * b:(i + 1) * b, 1:])
               for i in range(n_follow)]
    out = {}
    controls = control.split(",") if control else []
    for mode in ["f32"] + controls:
        t_mode, c0 = time.perf_counter(), ctx.clock.seconds
        w = compare.reference_weights(cell, ctx.seed, shard)
        fed = batches
        if mode == "half":      # the fault: half of the rows, mean over them
            fed, mode = [(t_[:b // 2], l_[:b // 2]) for t_, l_ in batches], "f32"
        got = ref.train_steps(
            w, cfg, fed, hyper, mode=mode, store=cfg["dtype"],
            row_block=int(chk["row_block"]),
            moments_on_host=bool(chk.get("moments_on_host")), put_rows=put_rows)
        start = compare.reference_weights(cell, ctx.seed, shard)
        got["change_norms"] = [
            float(ref._norm(a - b_)) for a, b_ in
            zip(ref.flat_leaves(w), ref.flat_leaves(start))]
        del w, start
        gc.collect()
        got["seconds"] = time.perf_counter() - t_mode
        got["compile_s"] = ctx.clock.seconds - c0
        out["half" if fed is not batches else mode] = got
    numbers = compare.train_numbers(program, out["f32"])
    numbers["reference_s"] = time.perf_counter() - t
    numbers["reference_losses"] = out["f32"]["losses"]
    numbers["reference_compile_s"] = out["f32"]["compile_s"]
    for mode in controls:
        ctl = compare.train_numbers(out[mode], out["f32"])
        numbers.setdefault("control", {})[mode] = {
            k: ctl[k] for k in ("loss_gap_max", "grad_norm_gap_max",
                                "change_norm_gap_max")}
    print(f"check {numbers}", flush=True)
    rows_, ok = compare.judge(numbers, chk["limits"])
    numbers["in_place"] = compare.judge_in_place(numbers.get("control", {}),
                                                 chk["limits"])
    return rows_, ok, numbers
