"""MXU-FLOPs accounting from lowered jaxprs.

Counts the 2*MAC FLOPs of every ``dot_general`` and
``conv_general_dilated`` in a traced function, recursing through
pjit/remat/custom-vjp wrappers and multiplying ``scan`` bodies by their
trip count: an honest-FLOPs count for a model whose FLOPs no closed
form gives, such as a conv net (nothing in the tree calls it but its
tests; the benchmark's counts are the families' closed forms;
reference analogue: the per-op flops registry behind
``python/paddle/distributed/auto_parallel/static/cost/estimate_cost.py``
and the profiler flops columns of ``tools/check_op_benchmark_result.py``).
"""

from __future__ import annotations

import math

import jax

__all__ = ["count_matmul_flops", "jaxpr_matmul_flops"]


def _dot_general_flops(eqn):
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    batch = math.prod(lhs[d] for d in lb) if lb else 1
    k = math.prod(lhs[d] for d in lc) if lc else 1
    m = math.prod(d for i, d in enumerate(lhs) if i not in set(lc) | set(lb))
    n = math.prod(d for i, d in enumerate(rhs) if i not in set(rc) | set(rb))
    return 2 * batch * m * n * k


def _conv_flops(eqn):
    dn = eqn.params["dimension_numbers"]
    groups = eqn.params.get("feature_group_count", 1)
    rhs = eqn.invars[1].aval.shape
    out = eqn.outvars[0].aval.shape
    # rhs_spec = (out_c dim, in_c/groups dim, *spatial)
    cin_per_group = rhs[dn.rhs_spec[1]]
    kernel = math.prod(rhs[d] for d in dn.rhs_spec[2:])
    # out elems already include out_c, batch, spatial; batch_group_count
    # rescales out_c, leaving the product correct
    return 2 * math.prod(out) * cin_per_group * kernel


def jaxpr_matmul_flops(jaxpr) -> int:
    """Total 2*MAC FLOPs of dot_general/conv ops in ``jaxpr`` (a Jaxpr or
    ClosedJaxpr).  ``while`` bodies count once (trip count is dynamic);
    ``cond`` counts its most expensive branch."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += eqn.params["length"] * \
                jaxpr_matmul_flops(eqn.params["jaxpr"])
        elif name == "while":
            total += jaxpr_matmul_flops(eqn.params["body_jaxpr"])
        elif name == "cond":
            total += max((jaxpr_matmul_flops(b)
                          for b in eqn.params["branches"]), default=0)
        else:
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    total += jaxpr_matmul_flops(sub)
                    break
    return total


def count_matmul_flops(fn, *args, **kwargs) -> int:
    """Trace ``fn`` (positional ``args`` may be Tensors or arrays) and
    return its total matmul/conv FLOPs."""
    from ..core.tensor import Tensor

    vals = [a._value if isinstance(a, Tensor) else a for a in args]
    jaxpr = jax.make_jaxpr(fn, **kwargs)(*vals)
    return jaxpr_matmul_flops(jaxpr)
