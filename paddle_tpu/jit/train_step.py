"""TrainStep — a fully-compiled training step.

The flagship perf path: forward + loss + backward + optimizer update traced
and compiled as ONE XLA program with donated buffers (params and optimizer
state update in place in HBM).  This is the TPU-native equivalent of the
reference's static-graph training executor (SURVEY §3.2): one fused program,
zero python per-op overhead, and — under a device mesh — GSPMD shards it
across DP/TP/PP axes from the layer/param sharding annotations.

Supported optimizers: SGD / Momentum / Adam / AdamW (the training recipes in
BASELINE.md).  Other optimizers fall back to `step_eager`.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core import generator as _generator
from ..core import tape as _tape
from ..core.tensor import Tensor
from ..observability import metrics as _obs
from ..observability.spans import span as _span
from ..ops.pallas._common import partitioned_scope
from ..optimizer import SGD, Adam, AdamW, Momentum
from ..optimizer.optimizer import Optimizer


_UNSET = object()


class _TrainStepInstruments:
    """Registry handles for the train-step hot path (shared across
    TrainStep instances; created once on first use).  A "compile" is
    the first dispatch of a (TrainStep, block size) pair — jax traces
    and XLA-compiles inside that call, so its wall time IS the compile
    duration (plus one step of execution, which is noise next to
    multi-second XLA compiles at real scale)."""

    _inst = None

    def __init__(self):
        r = _obs.get_registry()
        self.compiles = r.counter(
            "train_step.compiles", "XLA (re)compilations of the fused "
            "train step (first dispatch per executable)")
        self.compile_seconds = r.histogram(
            "train_step.compile_seconds",
            "trace + compile + first-step wall time",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                     120.0, 300.0))
        self.cache_hits = r.counter(
            "train_step.cache_hits", "dispatches served by an existing "
            "compiled executable")
        self.cache_misses = r.counter(
            "train_step.cache_misses", "dispatches that had to build an "
            "executable")
        self.step_seconds = r.histogram(
            "train_step.step_seconds", "per-call wall time of the "
            "compiled step (async dispatch; excludes compile calls)")

    @classmethod
    def get(cls):
        if cls._inst is None:
            cls._inst = cls()
        return cls._inst

    def record_dispatch(self, was_compile: bool, dt: float):
        """Account one dispatch: compile calls land in the compile
        histogram, steady-state calls in the step histogram."""
        if was_compile:
            self.compiles.inc()
            self.cache_misses.inc()
            self.compile_seconds.observe(dt)
        else:
            self.cache_hits.inc()
            self.step_seconds.observe(dt)


def _functional_sgd(p, g, state, lr, hp):
    # fp32 lr must not promote a bf16 param: cast the delta, not the result
    return p - (lr * g).astype(p.dtype), state


def _functional_momentum(p, g, state, lr, hp):
    v = state["velocity"]
    g = g.astype(v.dtype)
    v_new = hp["momentum"] * v + g
    if hp["nesterov"]:
        p_new = p - (lr * (g + hp["momentum"] * v_new)).astype(p.dtype)
    else:
        p_new = p - (lr * v_new).astype(p.dtype)
    return p_new, {"velocity": v_new}


def _stochastic_round_bf16(x, key):
    """Unbiased f32 -> bf16: add uniform 16-bit noise below the bf16
    mantissa boundary, then truncate (E[result] == x; plain
    round-to-nearest would bias an EMA that accumulates thousands of
    sub-ULP updates).

    Noise economics at 1.1B-param scale: threefry (jax.random.randint)
    costs ~40 ms/step of generation, and a full-size rng_bit_generator
    buffer is a 4.4 GB HBM transient (measured OOM).  Instead ONE small
    hardware-RBG tile per store is broadcast across leading dims.

    Within-step COLUMN CORRELATION (a property, not a bug): because the
    noise tile has only the trailing shape, every element sharing a
    trailing index (same "column", different leading rows) adds the
    SAME 16-bit noise value in a given step — their rounding errors are
    perfectly correlated within that step.  This sits next to the
    EMA-unbiasedness argument deliberately: unbiasedness needs
    per-element noise that is uniform and independent across STEPS
    (the fresh per-step key provides that), so E[m_t] per element is
    exact regardless of within-step correlation.  What the correlation
    DOES structure is same-step cross-element error: any consumer of a
    same-step spatial statistic over the stored moments (e.g. the
    variance of a column mean) sees column-correlated rounding noise,
    not i.i.d. noise.  The optimizer never computes such a statistic.

    SHAPE-PRESERVING (round 5): the round-4 form flattened x to
    [-1, 64Ki] around the noise add — on TPU that reshape physically
    relayouts the tiled array TWICE per moment store, which at 1.1B
    params was most of the optimizer sweep's 70-109 ms.  The noise tile
    is now one trailing-shape row broadcast across leading dims — pure
    elementwise traffic."""
    kd = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    seed = jnp.tile(kd, 2)[:4] if kd.size < 4 else kd[:4]
    x1 = x.reshape(1) if x.ndim == 0 else x
    bits = jax.lax.bitcast_convert_type(x1, jnp.uint32)  # x's own shape
    # one trailing row of noise, broadcast (for free, inside the update
    # fusion) across every leading dim
    _, tile = jax.lax.rng_bit_generator(seed, x1.shape[-1:],
                                        dtype=jnp.uint32)
    noise = (bits + (tile & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(noise, jnp.float32) \
        .astype(jnp.bfloat16).reshape(x.shape)


def _store_moment(val_f32, like, key):
    if like.dtype == jnp.float32:
        return val_f32
    if like.dtype == jnp.bfloat16 and key is not None:
        return _stochastic_round_bf16(val_f32, key)
    return val_f32.astype(like.dtype)


def _functional_adam(p, g, state, lr, hp, key=None):
    gf = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    b1, b2, eps, wd = hp["beta1"], hp["beta2"], hp["epsilon"], hp["wd"]
    if hp["decoupled"]:
        pf = pf * (1.0 - lr * wd)
    elif wd:
        gf = gf + wd * pf
    t = state["t"] + 1
    m = b1 * state["m"].astype(jnp.float32) + (1 - b1) * gf
    v = b2 * state["v"].astype(jnp.float32) + (1 - b2) * gf * gf
    m_hat = m / (1 - b1 ** t)
    v_hat = v / (1 - b2 ** t)
    from ..core.flags import flag
    if flag("adamw_rsqrt_update"):
        # Adam's epsilon-hat variant (Kingma & Ba, footnote to Alg. 1):
        # eps INSIDE the sqrt — update = m_hat * rsqrt(v_hat + eps^2).
        # Equivalent scale at v=0 and v>>eps^2 (differs by <= sqrt(2)
        # between); v5e's VPU divide+sqrt chain stalls the update sweep,
        # and hardware rsqrt measured 25% faster at 60M params
        p_new = (pf - lr * m_hat * jax.lax.rsqrt(v_hat + eps * eps)) \
            .astype(p.dtype)
    else:
        p_new = (pf - lr * m_hat / (jnp.sqrt(v_hat) + eps)).astype(p.dtype)
    if key is not None:
        km, kv2 = jax.random.split(key)
    else:
        km = kv2 = None
    return p_new, {"m": _store_moment(m, state["m"], km),
                   "v": _store_moment(v, state["v"], kv2), "t": t}


def _fused_adam_ok(update_fn, hypers, mesh):
    """Route the update sweep through the Pallas fused AdamW kernel:
    XLA's per-param update fusions measured ~170-230 GB/s effective on
    v5e while the native-shape fused kernel streams near the HBM
    roofline — the sweep is pure HBM traffic, so this nearly halves it.
    Round 4's flat-view kernel relayouted every tiled param (~520 MB of
    copies at 60M params, 89 GB/s effective — worse than XLA); the
    round-5 kernel grids over the param's OWN 2-D layout, so only
    natively tileable params route here (``native_tileable``).
    Single-chip only (a sharded param would need the kernel under
    shard_map) and decoupled-wd AdamW only (Adam folds wd into the
    grad, which the kernel does not model).  bf16 moments store via the
    hardware-PRNG stochastic rounding inside the kernel."""
    from ..core.flags import flag
    from ..ops.pallas._common import on_tpu
    # adamw_rsqrt_update changes the epsilon semantics of the XLA path;
    # the kernel implements only the reference sqrt form — mixing both
    # within one model would silently apply two different updates
    return (update_fn is _functional_adam and hypers.get("decoupled")
            and mesh is None and on_tpu()
            and not flag("adamw_rsqrt_update")
            and bool(flag("use_fused_adamw_kernel")))


def _fused_adam_eligible(p, s):
    """Per-param gate: native 2-D tileable shape, float param, moments in
    fp32 or bf16 (the kernel's SR path)."""
    from ..ops.pallas.fused_optimizer import native_tileable
    if not jnp.issubdtype(p.dtype, jnp.floating):
        return False
    if not isinstance(s, dict) or s.get("m") is None:
        return False
    if s["m"].dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return native_tileable(p.shape, p.dtype, s["m"].dtype)


def _fused_adam_update(p, g, state, lr, hp, key=None):
    from ..ops.pallas.fused_optimizer import fused_adamw_update
    t = state["t"] + 1
    seed = None
    if key is not None and state["m"].dtype == jnp.bfloat16:
        # i32 scalar seed for the kernel's hardware PRNG (fresh per step
        # via the step rng key; per-block offsets come from program ids)
        seed = jax.lax.bitcast_convert_type(
            jax.random.key_data(key).reshape(-1)[-1].astype(jnp.uint32),
            jnp.int32)
    p_new, m_new, v_new = fused_adamw_update(
        p, g, state["m"], state["v"], lr, t, beta1=hp["beta1"],
        beta2=hp["beta2"], epsilon=hp["epsilon"], weight_decay=hp["wd"],
        seed=seed)
    return p_new, {"m": m_new, "v": v_new, "t": t}


class TrainStep:
    def __init__(self, model, loss_fn: Callable, optimizer: Optimizer,
                 mesh=None, in_shardings=None, donate: bool = True,
                 accumulate_steps: int = 1, accumulate_avg: bool = True):
        """``accumulate_steps=k`` enables in-graph gradient merge
        (reference fleet gradient_merge meta-optimizer): every call
        accumulates grads into fp32 buffers; the optimizer applies them
        on each k-th call under ``lax.cond`` (averaged when
        ``accumulate_avg``) — zero host-side branching."""
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self._params = [p for p in model.parameters() if not p.stop_gradient]
        self._buffers = list(model.buffers())
        self._state = None
        self._compiled = None
        self._batch_sharding_cache = _UNSET
        self._update_fn, self._hypers = self._select_update(optimizer)
        if accumulate_steps < 1:
            raise ValueError(
                f"accumulate_steps must be >= 1, got {accumulate_steps}")
        self._accum_steps = accumulate_steps
        self._accum_avg = accumulate_avg
        self._gm_state = None

    def _select_update(self, opt):
        # multi_precision=False follows the reference contract: moments
        # live in the PARAM dtype (paddle adamw kernel's mp_ branch is
        # the fp32 path).  bf16 moments store via stochastic rounding —
        # plain round-to-nearest would bias the EMAs; with SR the
        # optimizer-state HBM sweep halves (BASELINE.md round 4).  The
        # noise tile is shared across leading dims, so same-step
        # rounding errors are COLUMN-correlated — unbiasedness per
        # element survives, same-step spatial statistics would not; see
        # the trade-off note in _stochastic_round_bf16's docstring
        if isinstance(opt, AdamW):
            return _functional_adam, {
                "beta1": opt._beta1, "beta2": opt._beta2,
                "epsilon": opt._epsilon, "wd": opt._weight_decay,
                "decoupled": True,
                "multi_precision": bool(getattr(opt, "_multi_precision",
                                                True))}
        if isinstance(opt, Adam):
            return _functional_adam, {
                "beta1": opt._beta1, "beta2": opt._beta2,
                "epsilon": opt._epsilon, "wd": opt._weight_decay,
                "decoupled": False,
                "multi_precision": bool(getattr(opt, "_multi_precision",
                                                True))}
        if isinstance(opt, Momentum):
            return _functional_momentum, {
                "momentum": opt._momentum, "nesterov": opt._use_nesterov}
        if isinstance(opt, SGD):
            return _functional_sgd, {}
        return None, None

    def _compile_probe(self, fn, flag_attr: str):
        """Closure that, called AFTER a dispatch of ``fn``, reports
        whether that dispatch traced+compiled: jit-cache growth when
        jax's private ``_cache_size`` probe exists (catches shape-change
        retraces too), else a first-dispatch flag on ``self``."""
        csize = getattr(fn, "_cache_size", None)
        if csize is not None:
            try:
                n0 = csize()
                return lambda: csize() > n0
            except Exception:
                pass
        first = not getattr(self, flag_attr, False)

        def probe():
            # flag set only here, AFTER a successful dispatch: if the
            # first dispatch raised, the retry still counts as compile
            setattr(self, flag_attr, True)
            return first

        return probe

    def _mesh(self):
        """Resolve mesh= (accepts jax Mesh, ProcessMesh, or None→global)."""
        if self.mesh is None:
            from ..distributed.topology import get_global_mesh
            return get_global_mesh()
        from ..distributed.sharding_api import _resolve_mesh
        return _resolve_mesh(self.mesh)

    def _opt_state_spec(self, p, mesh):
        """PartitionSpec for a param's optimizer state: inherit the param's
        sharding; under ZeRO (shard_optimizer) additionally shard the first
        free divisible dim over the 'sharding' axis (ZeRO-1 layout)."""
        from jax.sharding import PartitionSpec
        spec = list(p._dist_attr) if p._dist_attr is not None \
            else [None] * p._value.ndim
        while len(spec) < p._value.ndim:
            spec.append(None)

        def uses_axis(entry, name):
            return entry == name or (isinstance(entry, tuple) and name in entry)

        if getattr(self.optimizer, "_zero_sharded", False) and \
                "sharding" in mesh.axis_names and mesh.shape["sharding"] > 1 \
                and not any(uses_axis(e, "sharding") for e in spec):
            from ..distributed.sharding_api import shard_first_divisible_dim
            shard_first_divisible_dim(spec, p._value.shape,
                                      mesh.shape["sharding"])
        return PartitionSpec(*spec)

    def _opt_state_sharding(self, p):
        from jax.sharding import NamedSharding
        mesh = self._mesh()
        if mesh is None:
            return None
        return NamedSharding(mesh, self._opt_state_spec(p, mesh))

    def _place(self, arr, sharding):
        if sharding is None:
            return arr
        return jax.device_put(arr, sharding)

    def _init_state(self):
        def zeros_like_placed(p, dtype=None):
            arr = jnp.zeros(p._value.shape, dtype or p._value.dtype)
            return self._place(arr, self._opt_state_sharding(p))

        if self._update_fn is _functional_adam:
            # moment dtype: fp32 under multi_precision (default); with
            # multi_precision=False, bf16 params get bf16 moments (the
            # reference contract, stored via stochastic rounding).  fp16
            # params STAY fp32: fp16's 5-bit exponent overflows v at
            # |grad| > ~256, and the SR path is bf16-only
            def mdt(p):
                if self._hypers.get("multi_precision", True):
                    return jnp.float32
                return (jnp.bfloat16 if p._value.dtype == jnp.bfloat16
                        else jnp.float32)
            return [{"m": zeros_like_placed(p, mdt(p)),
                     "v": zeros_like_placed(p, mdt(p)),
                     "t": jnp.zeros((), jnp.float32)} for p in self._params]
        if self._update_fn is _functional_momentum:
            return [{"velocity": zeros_like_placed(p)}
                    for p in self._params]
        return [{} for _ in self._params]

    def _build(self):
        params = self._params
        update_fn = self._update_fn
        hypers = self._hypers
        model = self.model
        loss_fn = self.loss_fn
        grad_clip = self.optimizer._grad_clip

        # Output-sharding pins: keep updated params/state on their input
        # layouts so ZeRO sharding survives step 1 and donation holds.
        mesh = self._mesh()
        fused_adam = _fused_adam_ok(update_fn, hypers, mesh)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            # unannotated params pin REPLICATED: ZeRO stage-1/2 updates run
            # on opt-state shards, and this pin is the stage-1 post-update
            # all-gather — without it XLA would leave the new params
            # sharded (silently promoting the layout to stage-3)
            param_pins = [
                NamedSharding(mesh, PartitionSpec(*p._dist_attr))
                if p._dist_attr is not None
                else NamedSharding(mesh, PartitionSpec())
                for p in params
            ]
            state_pins = [NamedSharding(mesh, self._opt_state_spec(p, mesh))
                          for p in params]
        else:
            param_pins = [None] * len(params)
            state_pins = [None] * len(params)

        # ZeRO stage-2/3: gradients take the opt-state sharding (see the
        # constraint below at the value_and_grad site)
        grad_pins = None
        if mesh is not None and getattr(
                self.optimizer, "_group_sharded_level", None) in (
                    "os_g", "p_g_os"):
            grad_pins = [
                pin if pin is not None and any(
                    e is not None for e in self._opt_state_spec(p, mesh))
                else None
                for p, pin in zip(params, state_pins)]

        def pin(arr, sharding, like_shape):
            if sharding is None or arr.shape != like_shape:
                return arr
            return jax.lax.with_sharding_constraint(arr, sharding)

        buffers = self._buffers

        accum_steps = self._accum_steps
        accum_avg = self._accum_avg

        def compiled(p_values, opt_state, gm_state, rng_key, lr, b_values,
                     *inputs):
            def loss_of(pv):
                saved = [p._value for p in params]
                saved_b = [b._value for b in buffers]
                _generator.push_trace_key(rng_key)
                try:
                    for p, a in zip(params, pv):
                        p._value = a
                    for b, a in zip(buffers, b_values):
                        b._value = a
                    with _tape.no_grad():
                        out = loss_fn(model, *[Tensor(i) for i in inputs])
                    # mutable buffers (e.g. BatchNorm running stats) updated
                    # in-place during the traced forward come out as aux so
                    # no tracer leaks into module state
                    new_b = [b._value for b in buffers]
                finally:
                    for p, s in zip(params, saved):
                        p._value = s
                    for b, s in zip(buffers, saved_b):
                        b._value = s
                    _generator.pop_trace_key()
                loss_t = out[0] if isinstance(out, tuple) else out
                aux = out[1:] if isinstance(out, tuple) else ()
                return loss_t._value, (tuple(
                    a._value if isinstance(a, Tensor) else a
                    for a in aux), new_b)

            # over a mesh of several devices the step is a GSPMD-
            # partitioned program, into which no Pallas kernel may be
            # traced (ops/pallas/_common.pallas_enabled); the scope
            # spans the backward trace too (custom_vjp bwd rules)
            with partitioned_scope(mesh is not None and mesh.size > 1):
                (loss, (aux, new_b)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(list(p_values))
            if grad_pins is not None:
                # ZeRO stage-2/3 (os_g / p_g_os): pin each gradient to its
                # optimizer-state sharding so XLA reduce-scatters the grad
                # once and the whole update runs on 1/N shards — gradients
                # never materialize replicated (reference
                # group_sharded_stage2 reduce-scatter hooks)
                grads = [g if gpin is None else
                         jax.lax.with_sharding_constraint(g, gpin)
                         for g, gpin in zip(grads, grad_pins)]
            def apply_update(p_vals, grads_in, opt_in):
                gs = list(grads_in)
                if grad_clip is not None and hasattr(grad_clip, "clip_norm"):
                    gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in gs)
                    gnorm = jnp.sqrt(gsq)
                    cn = grad_clip.clip_norm
                    scale = cn / jnp.maximum(gnorm, cn)
                    gs = [g * scale.astype(g.dtype) for g in gs]
                new_p, new_s = [], []
                for i, (p, g, s) in enumerate(zip(p_vals, gs, opt_in)):
                    fn_i = (_fused_adam_update
                            if fused_adam and _fused_adam_eligible(p, s)
                            else update_fn)
                    if fn_i in (_functional_adam, _fused_adam_update) \
                            and isinstance(s, dict) \
                            and s.get("m") is not None \
                            and s["m"].dtype == jnp.bfloat16:
                        # bf16 moments store via stochastic rounding —
                        # a per-param key far from the dropout stream
                        np_, ns_ = fn_i(p, g, s, lr, hypers,
                                        key=jax.random.fold_in(
                                            rng_key, 1 << 20 | i))
                    else:
                        np_, ns_ = fn_i(p, g, s, lr, hypers)
                    np_ = pin(np_, param_pins[i], p.shape)
                    ns_ = {k: pin(v, state_pins[i], p.shape)
                           for k, v in ns_.items()}
                    new_p.append(np_)
                    new_s.append(ns_)
                return new_p, new_s

            if accum_steps == 1:
                new_p, new_s = apply_update(p_values, grads, opt_state)
                return new_p, new_s, gm_state, loss, aux, new_b

            # gradient merge: accumulate into fp32 buffers; the optimizer
            # fires on every accum_steps-th call under lax.cond (reference
            # gradient_merge_optimizer's conditional block)
            acc = [a + g.astype(jnp.float32)
                   for a, g in zip(gm_state["acc"], grads)]
            count = gm_state["count"] + 1
            fire = (count % accum_steps) == 0

            def fire_branch(operands):
                p_vals, opt_in, acc_in = operands
                gscale = (1.0 / accum_steps) if accum_avg else 1.0
                gs = [(a * gscale).astype(p.dtype)
                      for a, p in zip(acc_in, p_vals)]
                new_p, new_s = apply_update(p_vals, gs, opt_in)
                return (new_p, new_s, [jnp.zeros_like(a) for a in acc_in])

            def hold_branch(operands):
                p_vals, opt_in, acc_in = operands
                return (list(p_vals), list(opt_in), list(acc_in))

            new_p, new_s, new_acc = jax.lax.cond(
                fire, fire_branch, hold_branch,
                (list(p_values), list(opt_state), acc))
            return (new_p, new_s, {"acc": new_acc, "count": count},
                    loss, aux, new_b)

        jit_kwargs = dict(donate_argnums=(0, 1, 2))
        self._step_fn = compiled
        self._compiled = jax.jit(compiled, **jit_kwargs)

    def _batch_sharding(self):
        """NamedSharding for batch inputs: dim 0 over the 'data'
        (+'sharding' fused ZeRO-DP) axes, replicated elsewhere.  Depends
        only on the mesh — computed once and cached."""
        if self._batch_sharding_cache is not _UNSET:
            return self._batch_sharding_cache
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self._mesh()
        sharding = None
        n_shards = 1
        if mesh is not None:
            batch_axes = [a for a in ("data", "sharding")
                          if a in mesh.axis_names and mesh.shape[a] > 1]
            if batch_axes:
                for a in batch_axes:
                    n_shards *= mesh.shape[a]
                spec = PartitionSpec(tuple(batch_axes) if len(batch_axes) > 1
                                     else batch_axes[0])
                sharding = NamedSharding(mesh, spec)
        self._batch_sharding_cache = (sharding, n_shards)
        return self._batch_sharding_cache

    def _shard_batch(self, x):
        """Place a batch input over the data axes.  Inputs carrying an
        explicit user sharding annotation (Tensor._dist_attr) are respected
        and left untouched."""
        if isinstance(x, Tensor) and x._dist_attr is not None:
            return x._value
        arr = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        sharding, n_shards = self._batch_sharding()
        if sharding is None or arr.ndim == 0 or arr.shape[0] % n_shards != 0:
            return arr
        if getattr(arr, "sharding", None) == sharding:
            return arr
        return jax.device_put(arr, sharding)

    def _init_gm_state(self):
        if self._accum_steps == 1:
            return ()
        return {"acc": [self._place(jnp.zeros(p._value.shape, jnp.float32),
                                    self._opt_state_sharding(p))
                        for p in self._params],
                "count": jnp.zeros((), jnp.int32)}

    def run_steps(self, *inputs, steps: int):
        """Run ``steps`` consecutive train steps on the SAME batch inside
        ONE compiled call (``lax.scan`` over the step body, fresh RNG key
        per iteration, constant lr).  Amortizes per-dispatch host latency —
        benchmarking/microbenchmark use; real epochs feed fresh batches
        through ``__call__``.  Returns the last step's loss."""
        m = _TrainStepInstruments.get()
        if self._state is None:
            self._state = self._init_state()
            self._gm_state = self._init_gm_state()
            with _span("train_step.build"):
                self._build()
        if not hasattr(self, "_multi_cache"):
            self._multi_cache = {}
        fn = self._multi_cache.get(steps)
        if fn is None:
            step_fn = self._step_fn

            def multi(p_values, opt_state, gm_state, key, lr, b_values,
                      *inp):
                def body(carry, i):
                    p, s, gm, b, k = carry
                    k = jax.random.fold_in(k, i)
                    new_p, new_s, new_gm, loss, _aux, new_b = step_fn(
                        p, s, gm, k, lr, b, *inp)
                    return (list(new_p), list(new_s), new_gm,
                            list(new_b), k), loss

                carry0 = (list(p_values), list(opt_state), gm_state,
                          list(b_values), key)
                (p, s, gm, b, _k), losses = jax.lax.scan(
                    body, carry0, jnp.arange(steps))
                return p, s, gm, losses[-1], b

            fn = jax.jit(multi, donate_argnums=(0, 1, 2))
            self._multi_cache[steps] = fn
        arrays = [self._shard_batch(i) for i in inputs]
        key = _generator.default_generator().next_key()
        lr = jnp.float32(self.optimizer.get_lr())
        p_values = [p._value for p in self._params]
        b_values = [b._value for b in self._buffers]
        probe = self._compile_probe(fn, f"_dispatched_multi_{steps}")
        t0 = time.perf_counter()
        with _span("train_step.run_steps", steps=steps):
            new_p, self._state, self._gm_state, loss, new_b = fn(
                p_values, self._state, self._gm_state, key, lr, b_values,
                *arrays)
        m.record_dispatch(probe(), time.perf_counter() - t0)
        for p, v in zip(self._params, new_p):
            p._value = v
        for b, v in zip(self._buffers, new_b):
            b._value = v
        return Tensor(loss)

    def __call__(self, *inputs):
        m = _TrainStepInstruments.get()
        if self._state is None:
            self._state = self._init_state()
            self._gm_state = self._init_gm_state()
            with _span("train_step.build"):
                self._build()
        # a dispatch that grows the jit executable cache is a compile —
        # catches the first call AND input-shape-change retraces (which
        # would otherwise pollute the step-time histogram with
        # multi-second outliers); falls back to a first-dispatch flag
        # where the private _cache_size probe is unavailable
        probe = self._compile_probe(self._compiled, "_dispatched")
        arrays = [self._shard_batch(i) for i in inputs]
        key = _generator.default_generator().next_key()
        lr = jnp.float32(self.optimizer.get_lr())
        p_values = [p._value for p in self._params]
        b_values = [b._value for b in self._buffers]
        t0 = time.perf_counter()
        with _span("train_step.call"):
            new_p, self._state, self._gm_state, loss, aux, new_b = \
                self._compiled(
                    p_values, self._state, self._gm_state, key, lr,
                    b_values, *arrays)
        m.record_dispatch(probe(), time.perf_counter() - t0)
        for p, v in zip(self._params, new_p):
            p._value = v
        for b, v in zip(self._buffers, new_b):
            b._value = v
        loss_t = Tensor(loss)
        if aux:
            return (loss_t,) + tuple(Tensor(a) for a in aux)
        return loss_t
