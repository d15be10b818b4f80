"""Megatron tensor-parallel layers.

Analogue of ``python/paddle/distributed/fleet/layers/mpu/mp_layers.py``
(VocabParallelEmbedding:44, ColumnParallelLinear:312, RowParallelLinear:524,
ParallelCrossEntropy:729).

TPU-native design (GSPMD): each layer holds the FULL logical weight with a
sharding annotation over the "model" mesh axis.  Under jit on a mesh, GSPMD
splits the math and inserts the same collectives the reference codes by hand
(identity/allreduce pairs, vocab-parallel masked lookup + allreduce).  The
``gather_output`` / ``input_is_parallel`` flags become output/input sharding
constraints.  Eagerly on one device the layers behave like their serial
counterparts — matching the reference's world_size==1 fast path (mp_layers.py
falls back to F.linear when mp==1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .....core.tensor import Tensor
from ..... import nn
from .....nn import functional as F
from .....nn.layer.layers import Layer
from ....topology import get_global_mesh

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear",
           "RowParallelLinear", "ParallelCrossEntropy"]

MODEL_AXIS = "model"

# Leading (batch/seq) dims of activation constraints stay UNCONSTRAINED so
# GSPMD preserves whatever dp/sharding layout the caller established; pinning
# them to None (replicated) forces an involuntary full rematerialization
# (batch-sharded -> replicated reshard) on every constrained activation.
_U = PartitionSpec.UNCONSTRAINED


def _annotate(param, spec):
    param._dist_attr = spec
    mesh = get_global_mesh()
    if mesh is not None and MODEL_AXIS in mesh.axis_names and \
            not isinstance(param._value, jax.core.Tracer):
        param._value = jax.device_put(param._value,
                                      NamedSharding(mesh, spec))
    return param


def _constrain(x, spec):
    """Apply a sharding constraint under jit; no-op eagerly."""
    mesh = get_global_mesh()
    if mesh is None:
        return x
    from .....core.dispatch import dispatch

    def impl(a):
        if isinstance(a, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))
        return a

    return dispatch("sharding_constraint", impl, (x,))


class VocabParallelEmbedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        from .....nn.initializer import XavierNormal
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=XavierNormal())
        # vocab dim sharded over model axis (reference shards rows per rank)
        _annotate(self.weight, PartitionSpec(MODEL_AXIS, None))

    def forward(self, x):
        return F.embedding(x, self.weight)


class ColumnParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        from .....nn.initializer import XavierNormal
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=XavierNormal())
        _annotate(self.weight, PartitionSpec(None, MODEL_AXIS))
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            _annotate(self.bias, PartitionSpec(MODEL_AXIS))
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        if not self.gather_output:
            # keep activations sharded along the model axis (last dim)
            ndim = out.ndim
            out = _constrain(out, PartitionSpec(*([_U] * (ndim - 1)),
                                                MODEL_AXIS))
        return out


class RowParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        from .....nn.initializer import XavierNormal
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=XavierNormal())
        _annotate(self.weight, PartitionSpec(MODEL_AXIS, None))
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        if self.input_is_parallel:
            ndim = x.ndim
            x = _constrain(x, PartitionSpec(*([_U] * (ndim - 1)), MODEL_AXIS))
        # contraction dim sharded -> GSPMD inserts the allreduce the
        # reference does via mp_allreduce (mp_ops.py:285)
        out = F.linear(x, self.weight, self.bias)
        ndim = out.ndim
        # last dim un-sharded (the allreduce point); batch dims stay free
        return _constrain(out, PartitionSpec(*([_U] * (ndim - 1)), None))


class ParallelCrossEntropy(Layer):
    """Vocab-parallel softmax cross-entropy (reference mp_layers.py:729 /
    _c_softmax_with_cross_entropy).  With logits sharded over the vocab dim,
    the fused log-softmax + gather below lets GSPMD keep the reduction local
    and emit one allreduce of scalars — same comm volume as the reference's
    custom kernel."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        from .....core.dispatch import dispatch
        ignore_index = self.ignore_index

        def impl(logits, lbl):
            lse = jax.scipy.special.logsumexp(
                logits.astype(jnp.float32), axis=-1)
            idx = lbl.astype(jnp.int32)
            squeeze = idx.ndim == logits.ndim
            if squeeze:
                idx = idx[..., 0]
            picked = jnp.take_along_axis(
                logits.astype(jnp.float32), idx[..., None], axis=-1)[..., 0]
            loss = lse - picked
            if ignore_index >= 0:
                loss = jnp.where(idx == ignore_index, 0.0, loss)
            return loss[..., None] if squeeze else loss

        return dispatch("parallel_cross_entropy", impl, (input, label),
                        nondiff_mask=[False, True])
