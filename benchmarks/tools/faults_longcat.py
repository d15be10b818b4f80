"""Faults of the LongCat-Flash decoder's own mechanisms, planted in the program
underneath a run as ``tools/faults.py`` plants the general ones; ``correct``
has to come out false for each.

    python3 benchmarks/tools/faults_longcat.py --fault sigmoid_router \
        --workload longcat_reason_sat --seed ... --seconds ... --trace 0

This file adds its faults to ``tools/faults.py``'s table and hands over to
``tools/control_run.py``, so ``--control`` works here too.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.tools.faults import FAULTS, _patched  # noqa: E402


def zero_experts_left_out():
    """A pick of a zero-compute expert adds nothing, as if its expert were
    absent: the identity's ``w_e u`` is left out."""
    from paddle_tpu.nn.layer import experts
    honest = experts.grouped_experts
    return _patched(experts, "grouped_experts",
                    lambda *args: honest(*args[:7]))


def moe_from_second_sublayer():
    """The MoE reads the second sublayer's post-attention norm (a plain MoE
    in place of the second feed-forward's input) instead of the first's."""
    from paddle_tpu.models import longcat_flash

    def step(self, x, pos0, n_valid, cache, live=None):
        kv0, kv1 = (None, None) if cache is None else cache
        y, kv0 = self.self_attn[0].step(self.input_layernorm[0](x), pos0,
                                        n_valid, kv0)
        h = x + y
        h = h + self.mlps[0](self.post_attention_layernorm[0](h))
        y, kv1 = self.self_attn[1].step(self.input_layernorm[1](h), pos0,
                                        n_valid, kv1)
        h = h + y
        u = self.post_attention_layernorm[1](h)
        m, load = self.moe(u, live)
        return h + self.mlps[1](u) + m, (kv0, kv1), load

    return _patched(longcat_flash.LongCatFlashDecoderLayer, "step", step)


def k_pe_not_rotated():
    """The shared positional key cached as projected, not rotated at its
    position (the query's positional part still is)."""
    from paddle_tpu.models import mla
    honest = mla.rotate_pairs
    # k_pe is [B, C, rope], a query's positional part [B, C, heads, rope]
    return _patched(mla, "rotate_pairs",
                    lambda x, pos, inv_freq: x if x.ndim == 3
                    else honest(x, pos, inv_freq))


def lora_scales_left_out():
    """Neither LoRA scale: q and the normed latent as projected."""
    from paddle_tpu.models import mla
    honest = mla.LatentAttention.__init__

    def init(self, *args, **kw):
        honest(self, *args, **dict(kw, q_scale=1.0, kv_scale=1.0))

    return _patched(mla.LatentAttention, "__init__", init)


def sigmoid_router():
    """A sigmoid for each router output in place of one softmax over all of
    them."""
    from paddle_tpu.nn.layer import experts
    honest = experts.route_tokens
    return _patched(experts, "route_tokens",
                    lambda u, router, bias, top_k, **kw: honest(
                        u, router, bias, top_k, **dict(kw, scoring="sigmoid")))


def top_k_less_one():
    """One expert fewer a token than the configuration says (top-11 for
    top-12)."""
    from paddle_tpu.nn.layer import experts
    honest = experts.route_tokens
    return _patched(experts, "route_tokens",
                    lambda u, router, bias, top_k, **kw: honest(
                        u, router, bias, top_k - 1, **kw))


LONGCAT_FAULTS = {"zero_experts_left_out": zero_experts_left_out,
                  "moe_from_second_sublayer": moe_from_second_sublayer,
                  "k_pe_not_rotated": k_pe_not_rotated,
                  "lora_scales_left_out": lora_scales_left_out,
                  "sigmoid_router": sigmoid_router,
                  "top_k_less_one": top_k_less_one}


if __name__ == "__main__":
    from benchmarks.tools import control_run
    FAULTS.update(LONGCAT_FAULTS)
    sys.exit(control_run.main())
