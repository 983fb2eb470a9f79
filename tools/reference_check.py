#!/usr/bin/env python
"""Hold the served path of a hybrid model to its plain reference, in logits.

    python tools/reference_check.py --config lfm2-8b-a1b-int8     # the chip
    python tools/reference_check.py --config ax-k1-int8-ep16      # the chip
    python tools/reference_check.py --config k-exaone-int8-ep8    # the chip
    python tools/reference_check.py --config smallthinker-21ba3b-int8  # the chip
    python tools/reference_check.py --config brumby-14b-int8  # the chip: a
        # model served WITHOUT a pool (retention_probes, below)
    python tools/reference_check.py --config nemotron3-super-int8-ep4
    python tools/reference_check.py --config qwen3-next-int8-ep4  # the chip:
        # a state BESIDE a pool, through the same legs
    python tools/reference_check.py --config qwen2-7b-int8  # the chip: the
        # prefix cache's probes (prefix_cache_probes, below), and nothing else
    JAX_PLATFORMS=cpu python tools/reference_check.py --rehearsal # lfm2-tiny
    (--config ax-k1-int8-ep16 --rehearsal: ax-k1-tiny; --config
    k-exaone-int8-ep8 --rehearsal: k-exaone-tiny; --config
    smallthinker-21ba3b-int8 --rehearsal: smallthinker-tiny; --config
    brumby-14b-int8 --rehearsal: brumby-tiny; --config qwen3-next-int8-ep4
    --rehearsal: qwen3-next-tiny)

For each of the benchmark's four probe prompts the tool takes the logits
the SERVED path produces, admission at the prompt's own bucket and then 8
decode steps through the page pool and the state beside it (convolution
state, the windowed layers' rings), in a batch
slot of a pool shaped as the cell serves it, and compares them with the
reference's full forward (models/reference/lfm2_moe.py; axk1.py for a
model with latent attention and exaone_moe.py for one of windowed and full
attention layers, which get the chip's share of the experts
the configuration holds: float32 at ``highest`` precision, no cache, no
kernel) over the same tokens.  A model with windowed layers gets a fifth
probe of ``LONG_PROBE`` bytes, which its 8,192 bucket admits in blocks
(its reference then scores the queries 512 at a time); one whose window no
other probe reaches (SmallThinker's 4,096) a sixth of window - 4 bytes, so
that the 8 decode steps behind it cross the window and the ring wraps
WHILE DECODING.  Both
sides hold the same seed-0 int8 weights; the reference gets them
dequantized a layer at a time and never holds more than one layer in
float32.

Two comparisons a probe, because a network of random weights amplifies a
rounding of its activations sixty-fold (PERF.md, PR 28) and bfloat16 alone
then moves the logits by a quarter of their spread:

- ``mechanism``: the served path with float32 activations at ``highest``
  precision (the same programs' code, weights, kernels, pages and state).
  What is left is summation order, so the tolerance is tight, and a dropped
  expert, a bias that weighs, a state taken at the bucket's end or a page
  read from the wrong layer cannot pass it.
- ``as_served``: bfloat16 activations, as the cell runs.  It says how far
  the precision moves the logits, and its first-token logprob decides where
  the benchmark's golden comes from.

The served logits come from two small programs built from the functions
the batcher's own programs are built from (``_prefill_row`` +
``_paged_splice``; the forward call of ``_decode_steps``), because
``admit_row_paged`` and ``decode_chunk`` hand out tokens and logprobs, not
logits.  The batcher itself then serves the same probe, and its tokens and
logprobs must be the ones those logits give: that ties what is compared
here to the programs the benchmark times.

Last, one control from the SERVED path: the expert stacks moved onto the
int4 grid where they lie (the precision below the configuration's, still
read by the int8 kernel) and the ``as_served`` leg run again on the first
probe, fed the same tokens.  It has to land outside the ``as_served``
tolerances, or they would pass a 4-bit expert leg in the timed programs.

Tolerances (``TOL``), why, and what would break them are in PERF.md,
section 6, PR 28.  Writes ``chiprun_out/reference_check/<config>.json``
(every number) and ``.golden.json`` (the reference's logprobs of the served
tokens, in the benchmark's golden format).  Exit 0 iff every probe is
inside every tolerance.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from functools import partial

HERE = ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if "--tree" in sys.argv:  # another checkout's programs (a parent's, _chip/)
    ROOT = os.path.abspath(sys.argv[sys.argv.index("--tree") + 1])
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.run import PROBE_BYTES, PROBE_TOKENS, probe_prompt  # noqa: E402

# Each limit lies between what the chip gave and what a wrong model gives
# on the same probe (PERF.md, section 6, PR 28, has both readings).
TOLS = {
  "lfm2-8b-a1b-int8": {
    # float32 activations: only the order of summation differs.  The chip
    # gave 7.7e-5 / 1.3e-5 at the most; three experts a token for four give
    # 3.35 / 0.56, int4 weights 5.69 / 0.95.
    "mechanism": {"max_abs_logit": 2e-3, "mean_abs_logit": 2e-4},
    # bfloat16 activations through 24 layers that amplify: the chip gave
    # 1.79 / 0.24 at the most over the four probes (the same to the last
    # digit run after run), and 2.14 / 0.34 on probe 32 with the expert
    # stacks on the int4 grid in the SERVED programs (the control at the
    # end of main): each limit lies between its two readings.
    "as_served": {"max_abs_logit": 1.96, "mean_abs_logit": 0.29},
  },
  "ax-k1-int8-ep16": {
    # float32 activations, latent pages in float32: the order of summation
    # (the absorbed form against expanded heads).  The chip gave 3.6e-4 /
    # 5.7e-5 at the most (probe 1500); seven experts a token for eight give
    # 0.93 / 0.098, no groups 0.91 / 0.11, int4 weights 2.79 / 0.50.
    "mechanism": {"max_abs_logit": 2e-3, "mean_abs_logit": 2e-4},
    # bfloat16 activations through 13 layers: the chip gave 0.390 / 0.044
    # at the most over the four probes (0.334 / 0.044 on probe 32), and
    # 0.564 / 0.081 on probe 32 with the held experts' stacks on the int4
    # grid in the SERVED programs: each limit lies between its readings.
    "as_served": {"max_abs_logit": 0.47, "mean_abs_logit": 0.062},
  },
  "k-exaone-int8-ep8": {
    # float32 activations, pages and rings in float32: the order of
    # summation (a ring in slot order, a prefix in pages, the flash
    # kernel's tiles against one masked score matrix).  The chip gave
    # 9.6e-5 / 1.5e-5 at the most (the 6,000-byte probe; 1.7e-5 / 2.8e-6
    # on probe 32); on probe 200 seven experts a token for eight give 0.59
    # / 0.089, rotation on the full layers 0.57 / 0.070, no QK-norm 0.97 /
    # 0.15, a window of 256 1.71 / 0.29, int4 weights 2.89 / 0.50.
    "mechanism": {"max_abs_logit": 2e-3, "mean_abs_logit": 2e-4},
    # bfloat16 activations through 12 layers: the chip gave 0.525 / 0.059
    # at the most over the five probes (probe 32; 0.411 / 0.018 on the
    # 6,000-byte one), and 0.808 / 0.092 on probe 32 with the held experts'
    # stacks on the int4 grid in the SERVED programs: each limit lies
    # between its readings.  (A rotation on the full layers moves the
    # reference by 0.57 / 0.070, inside these: the mechanism leg tells it.)
    "as_served": {"max_abs_logit": 0.65, "mean_abs_logit": 0.075},
  },
  "smallthinker-21ba3b-int8": {
    # float32 activations, pages and rings in float32: the order of
    # summation (a ring in slot order and walked in blocks, a prefix in
    # pages, the flash kernel's tiles against one masked score matrix).
    # The chip gave 5.2e-5 / 8.0e-6 at the most (the 6,000-byte probe); on
    # probe 200 three experts a token for six give 1.73 / 0.23, the router
    # on the FFN norm's output 3.09 / 0.52, silu for relu 1.33 / 0.21,
    # rotation on the full layers 1.96 / 0.30, int4 weights 2.56 / 0.41.
    "mechanism": {"max_abs_logit": 2e-3, "mean_abs_logit": 2e-4},
    # bfloat16 activations through 12 layers: the chip gave 0.414 / 0.028
    # at the most over the six probes (probe 200; 0.064 / 0.010 on the
    # 6,000-byte one, 0.116 / 0.015 on the 4,092-byte one), and 1.134 /
    # 0.170 on probe 32 with the expert stacks on the int4 grid in the
    # SERVED programs: each limit lies between its readings.
    "as_served": {"max_abs_logit": 0.70, "mean_abs_logit": 0.070},
  },
  "brumby-14b-int8": {
    # float32 activations, the state float32, both kernels contracting at
    # full precision: the order of summation (the chunked scan and the
    # recurrence against one [T, T] matrix of weights) AND the rotation's
    # angle, position x frequency in float32, whose rounding grows with the
    # position and is felt a hundred times more here than under a softmax:
    # q and k are RMS-normalised (|q| |k| = 128), nothing scales q . k, and
    # a weight is its SQUARE.  The chip gave 6.1e-5 / 7.0e-6 on probe 32,
    # 8.6e-4 / 1.2e-4 on probe 1500 and 3.5e-3 / 4.7e-4 on the 6,000-byte
    # one (the most); with the state held in bfloat16 between the programs
    # the same leg gives 0.148 / 0.0173 at the LEAST over the five probes
    # (0.211 / 0.0193 at the most); on probe 200 the power 3 gives 6.94 /
    # 1.12, no gate 6.90 / 1.05, no QK-norm 1.68 / 0.23, no rotation 3.93
    # / 0.63, int4 weights 4.93 / 0.65.  Each limit lies six times over the
    # reading and seven under the control's least.
    "mechanism": {"max_abs_logit": 2e-2, "mean_abs_logit": 2.5e-3},
    # bfloat16 activations through 10 layers: the chip gave 0.630 / 0.0583
    # at the most over the five probes (the maximum on probe 32, the mean on
    # probe 700; the same to the last digit in two calls), and 0.548 /
    # 0.0625 at the most with the state held in bfloat16 (the maximum on
    # probe 200, the mean on probe 1500): ONE rounding of the state is one
    # more bfloat16 rounding among a dozen a layer, so the control moves
    # the mean by 7% and the maximum not at all, and only the mean's limit
    # can lie between its two readings (outside by one limit, not by each).
    # The mechanism leg above tells the same control by a factor of 40.
    "as_served": {"max_abs_logit": 0.80, "mean_abs_logit": 0.0604},
  },
  "nemotron3-super-int8-ep4": {
    # float32 activations, the state float32, the scan's kernels contracting
    # at full precision: the order of summation (a chunk's [128, 128] tile
    # and the state between chunks against one step a token; pairs grouped
    # by expert against a loop over the experts; the flash kernel's tiles)
    # AND the router's choice: 22 of 512 sigmoid scores lie a thousandth
    # apart, so a rounding in the seventh digit changes the 22nd pick of
    # about one token a layer in a thousand, the more tokens the more often,
    # and a changed pick moves that token's hidden state and, through the
    # states, every later one's.  The chip gave 3.0e-5 / 5.0e-6 on probe 32,
    # 3.4e-3 / 3.4e-4 on probe 1500 and 1.25e-2 / 1.74e-3 on the 6,000-byte
    # one (the most); with the state held in bfloat16 between the programs
    # the same leg gives 0.823 / 0.0386 at the most over the three probes
    # (probe 32; 0.450 / 0.0157 on the 6,000-byte one, 0.0087 / 0.00104 on
    # probe 1500, whose 8 steps' roundings mostly cancel); on probe 1500 one
    # expert fewer a token gives 1.53 / 0.229, silu for relu^2 2.66 / 0.459,
    # the latent left out 4.34 / 0.699, the norm before the gate 3.72 /
    # 0.617, no convolution bias 2.67 / 0.465, int4 weights 3.66 / 0.618
    # (against the as-served leg's tokens).
    # Each limit lies four times over the sound readings' most and six
    # times (the mean) to sixteen (the maximum) under the control's most.
    "mechanism": {"max_abs_logit": 5e-2, "mean_abs_logit": 6e-3},
    # bfloat16 activations through 22 sub-layers whose expert layers weigh
    # their routed sum by 5: the chip gave 1.408 / 0.1896 at the most over
    # the three probes (probe 1500; 1.297 / 0.1753 on probe 32, 1.293 /
    # 0.1525 on the 6,000-byte one), and 1.401 / 0.1999 at the most with the
    # state held in bfloat16 (probe 1500): ONE rounding of the state is one
    # more bfloat16 rounding among dozens a token, so the control moves the
    # mean by 5% and the maximum not at all, and only the mean's limit can
    # lie between its two readings (outside by one limit, not by each).
    # The mechanism leg above tells the same control by a factor of 22.  The
    # wrong models of that leg lie outside the mean's too (0.229 the least:
    # one expert of 22 fewer; the others 0.46-0.70).
    "as_served": {"max_abs_logit": 1.9, "mean_abs_logit": 0.195},
  },
  "qwen3-next-int8-ep4": {
    # float32 activations, the state float32, the scan's kernels contracting
    # at full precision: the order of summation (a chunk's triangle, inverted
    # by blocks, and the state between chunks against one step a token; pairs
    # grouped by expert against a loop over the experts; the flash kernel's
    # tiles) and, as in nemotron, the router's choice: 10 of 512 softmax
    # scores lie close, so a rounding in the seventh digit changes a token's
    # tenth pick now and then, the more tokens the more often.  The chip gave
    # 1.0e-4 / 1.6e-5 on probe 32, 1.76e-3 / 2.9e-4 on probe 1500 and 5.13e-3
    # / 8.4e-4 on the 6,000-byte one (the most); with the state held in
    # bfloat16 between the programs the same leg gives 0.142 / 0.0091 at the
    # LEAST over the three probes (probe 1500; 0.144 / 0.0088 on the
    # 6,000-byte one, 0.396 / 0.0273 on probe 32); on probe 1500 nine picks
    # for ten give 1.23 / 0.198, the attention's gate left off 2.05 / 0.330,
    # the shared expert's gate left off 5.42 / 0.866, beta fixed at 1 4.83 /
    # 0.837, the gate before the norm 4.56 / 0.781, int4 weights 4.38 / 0.738.
    # Each limit lies three and a half (the mean) to five times (the maximum)
    # over the sound readings' most and three to five times under the
    # control's least.
    "mechanism": {"max_abs_logit": 2.5e-2, "mean_abs_logit": 3e-3},
    # bfloat16 activations through 12 layers: the chip gave 0.745 / 0.1104 at
    # the most over the three probes (probe 32; 0.544 / 0.0852 on probe 1500,
    # 0.499 / 0.0747 on the 6,000-byte one).  Each limit lies between that
    # and the LEAST wrong model of the mechanism leg's list (nine picks for
    # ten, 1.23 / 0.198).  The state held in bfloat16 CANNOT be told in this
    # leg, by either limit: it gives 0.736 / 0.1085, 0.624 / 0.0898 and 0.499
    # / 0.0724 on the three probes, over the sound reading on one and under
    # it on two: one rounding of a state whose every input is bfloat16
    # already is one more among dozens a token (brumby's and nemotron's legs
    # told it by 7 and 5% of the mean, a margin that is not there here).
    # ``state_in_as_served`` False says so: that control is reported in this
    # leg and REQUIRED to fail in the mechanism leg, which tells it by a
    # factor of 11 (the mean, at the least) to 79 (the maximum, at the most).
    "as_served": {"max_abs_logit": 0.95, "mean_abs_logit": 0.15},
    "state_in_as_served": False,
  },
}
# A dense model's continuation behind cached pages (prefix_cache_probes): the
# hit against the same prompt served with the cache off, and each against the
# plain float32 reference, in the chosen tokens' logprobs (the batcher hands
# out no logits).  The chip gave, parent | change (my chip runs, PR 47;
# PERF.md, section 6, PR 47), the 8 tokens equal on every side: hit against
# fresh 0.0167 | 0.0188 at the most over the 8 logprobs; the first token's
# against the reference 0.0030 | 0.0063 (hit) and 0.0082 | 0.0082 (fresh:
# the same program on both sides); over all 8, 0.014 | 0.020 (hit) and
# 0.013 (fresh).  Both limits are the benchmark's own on a cached answer
# against a fresh one (benchmark/run.py GOLDEN_TOL, which its warm-up holds
# every shared run to): the dense body and the kernel sum in different
# orders, and a seed-0 model's logprobs lie within 0.3 of one another, so a
# limit much under it would be the rounding's and one much over it nobody's.
PREFIX_TOLS = {
  "qwen2-7b-int8": {"hit_against_fresh": 0.05, "against_reference": 0.05},
}
PREFIX_PROBE = (1200, 100)  # bytes: the document, a question behind it
LONG_PROBE = 6000  # bytes: 46 wraps of a 128-token ring, 94 pages deep
#   (one wrap of a 4,096-token ring, in the admission)
GOLDEN_FROM_REFERENCE = 0.025  # half of benchmark/run.py GOLDEN_TOL


def reference_cfg(cfg) -> dict:
    """What the plain references read, under their own (the published)
    names: lfm2_moe's keys, and axk1's beside them."""
    yarn = cfg.rope_scaling_type == "yarn" and cfg.rope_scaling_factor != 1.0
    return dict(
        norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        layer_types=cfg.layer_types, num_dense_layers=cfg.num_dense_layers,
        num_experts_per_token=cfg.num_experts_per_token,
        norm_topk_prob=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.moe_routed_scale,
        num_heads=cfg.num_heads, kv_lora_rank=cfg.kv_lora_rank,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
        sliding_window=cfg.sliding_window, qk_norm=cfg.qk_norm,
        full_rope=cfg.attn_rope, gate_act=cfg.gate_act,
        router_input=cfg.moe_router_input, score_fn=cfg.moe_score_fn,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group,
        ssm_heads=cfg.ssm_heads, ssm_head_dim=cfg.ssm_head_dim,
        ssm_groups=cfg.ssm_groups, ssm_state=cfg.ssm_state,
        conv_kernel=cfg.ssm_conv_kernel,
        gdn_key_heads=cfg.gdn_key_heads, gdn_value_heads=cfg.gdn_value_heads,
        gdn_key_dim=cfg.gdn_key_dim, gdn_value_dim=cfg.gdn_value_dim,
        gdn_conv_kernel=cfg.gdn_conv_kernel, rotary_pct=cfg.rotary_pct,
        rope_scaling=dict(
            factor=cfg.rope_scaling_factor,
            original_max_position_embeddings=cfg.rope_original_max_len,
            beta_fast=cfg.yarn_beta_fast, beta_slow=cfg.yarn_beta_slow,
            mscale=cfg.yarn_mscale, mscale_all_dim=cfg.yarn_mscale_all_dim,
        ) if yarn else None,
    )


class _ExpertsOneAtATime:
    """An expert stack [E, K, N] (quantized or not) that hands the
    reference expert ``e`` in float32 when it asks for it: A.X-K1's 12
    held experts of a layer are 2.1 GB in float32 at once."""

    def __init__(self, stack, floats):
        self.stack, self.floats = stack, floats
        self.shape = getattr(stack, "unpacked_shape", None) or stack.shape

    def __getitem__(self, e):
        return self.floats(jax.tree.map(lambda a: a[e], self.stack))


@jax.jit
def _round_to_int4(w):
    """``w`` rounded to 4 bits with 128-wide absmax blocks along its last
    axis (checkpoint.quantize's int4 grid), in one fused pass."""
    n = w.shape[-1]
    blocks = w.reshape(*w.shape[:-1], n // 128, 128) if n % 128 == 0 \
        else w[..., None, :]
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 7.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jnp.clip(jnp.round(blocks / scale), -7, 7) * scale).reshape(w.shape)


@partial(jax.jit, donate_argnums=(0,))
def _int8_on_int4_grid(q):
    """int8 block data moved onto the 15 levels of the int4 grid of the
    same absmax blocks (a block's largest value is 127 on the one grid and
    7 on the other), in place: what the stack would hold had it been
    quantized to 4 bits, in the form the int8 kernel reads."""
    levels = jnp.round(q.astype(jnp.float32) * (7.0 / 127.0))
    return jnp.round(levels * (127.0 / 7.0)).astype(jnp.int8)


def reference_logits(params, cfg, tokens, int4=False, **changed):
    """The reference's logits [T, V]; the layers are dequantized as the
    reference asks for them, one at a time.  ``int4`` rounds every block
    weight to 4 bits on the way (the precision below the configuration's);
    ``changed`` overrides keys of the reference's configuration."""
    from distributed_llms_tpu.checkpoint import quantize as quant_lib
    from distributed_llms_tpu.models import model as model_lib
    from distributed_llms_tpu.models.reference import (
        axk1, brumby, exaone_moe, lfm2_moe, nemotron_h, qwen3_next,
        smallthinker)

    def floats(tree):
        def one(x):
            if not isinstance(x, quant_lib.QuantizedTensor):
                return x
            w = quant_lib.dequantize(x, jnp.float32)
            return _round_to_int4(w) if int4 else w

        return jax.tree.map(
            one, tree,
            is_leaf=lambda x: isinstance(x, quant_lib.QuantizedTensor))

    def layer(p):
        ex = (p["mlp"].pop("experts", None)
              if cfg.experts_held is not None and p["mlp"] else None)
        p = floats(p)
        if ex is not None:
            p["mlp"]["experts"] = {
                k: _ExpertsOneAtATime(v, floats) for k, v in ex.items()}
        return p

    lazy = {k: v for k, v in params.items() if k != "blocks"}
    lazy["layers"] = (layer(p) for p in model_lib.hybrid_layers(params, cfg))
    ref_cfg = {**reference_cfg(cfg), **changed}
    toks = jnp.asarray(tokens, jnp.int32)
    if cfg.ret_layers:
        return brumby.forward(
            lazy, ref_cfg, toks, 512 if len(toks) > 2048 else None)
    held = (None if cfg.experts_held is None
            else (cfg.experts_offset, cfg.experts_held))
    if cfg.ssm_layers or cfg.gdn_layers:
        return (nemotron_h if cfg.ssm_layers else qwen3_next).forward(
            lazy, ref_cfg, toks, experts_held=held,
            query_block=512 if len(toks) > 2048 else None)
    if not (cfg.kv_lora_rank or cfg.swa_layers):
        return lfm2_moe.forward(lazy, ref_cfg, toks)
    if cfg.kv_lora_rank:
        return axk1.forward(lazy, ref_cfg, toks, experts_held=held)
    query_block = 512 if len(toks) > 2048 else None
    if cfg.moe_router_input == "block_input":
        return smallthinker.forward(lazy, ref_cfg, toks, query_block)
    return exaone_moe.forward(
        lazy, ref_cfg, toks, experts_held=held, query_block=query_block)


def dense_reference_logits(params, cfg, tokens):
    """A plain forward of the llama family (qwen2: biases on q, k and v) in
    float32 under the caller's matmul precision, no kernel, no cache, no
    scan: RMS norms, rotate-half RoPE over the whole head, grouped-query
    causal softmax of q k^T / sqrt(head_dim), SwiGLU, an untied head.  One
    layer's weights are dequantized at a time.  -> [T, V]."""
    from distributed_llms_tpu.checkpoint import quantize as quant_lib

    is_q = lambda x: isinstance(x, quant_lib.QuantizedTensor)
    floats = lambda tree: jax.tree.map(
        lambda x: (quant_lib.dequantize(x, jnp.float32) if is_q(x)
                   else jnp.asarray(x, jnp.float32)), tree, is_leaf=is_q)
    rms = lambda x, w: w * x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + cfg.norm_eps)
    toks = jnp.asarray(tokens, jnp.int32)
    t, hd, g = len(toks), cfg.head_dim_, cfg.num_heads // cfg.num_kv_heads
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):  # [T, H, hd]
        x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((t, t), bool))
    x = jnp.asarray(params["embed"]["wte"], jnp.float32)[toks]
    for layer in range(cfg.num_layers):
        p = floats(jax.tree.map(lambda a: a[layer], params["blocks"],
                                is_leaf=lambda a: False))
        a, h = p["attn"], rms(x, p["ln1"]["scale"])
        q, k, v = (jnp.einsum("td,dhk->thk", h, a[w]) + a.get(b, 0.0)
                   for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
        q, k = rope(q), jnp.repeat(rope(k), g, axis=1)
        s = jnp.einsum("thk,shk->hts", q, k) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shk->thk", pr, jnp.repeat(v, g, axis=1))
        x = x + jnp.einsum("thk,hkd->td", o, a["wo"])
        m, h = p["mlp"], rms(x, p["ln2"]["scale"])
        x = x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
    head = (params["embed"]["wte"].T if cfg.tie_embeddings
            else params["lm_head"]["w"])
    return rms(x, jnp.asarray(params["final_norm"]["scale"], jnp.float32)) \
        @ jnp.asarray(head, jnp.float32)


def prefix_cache_probes(a, config) -> int:
    """A dense configuration's probes with the prefix cache ON (PR 47): a
    document of ``PREFIX_PROBE[0]`` bytes with a question of
    ``PREFIX_PROBE[1]`` served cold; a second question on the same document,
    which HITS the first one's pages and is admitted as a row's continuation
    (``admit_row_auto_paged``; on the chip the flash kernel scores it); and
    the same second prompt with the cache off.  The hit's 8 chosen-token
    logprobs against the cold-served ones of the same prompt, and both
    against the plain float32 reference's logprobs of the same tokens."""
    from distributed_llms_tpu.core.observability import METRICS
    from distributed_llms_tpu.models import model as model_lib
    from distributed_llms_tpu.models.presets import get_preset
    from distributed_llms_tpu.runtime import batcher as B
    from distributed_llms_tpu.runtime.tokenizer import get_tokenizer

    serve, preset = dict(config["serve"]), config["preset"]
    doc_bytes, q_bytes = PREFIX_PROBE
    if a.rehearsal:
        preset, (doc_bytes, q_bytes) = "llama-tiny", (70, 9)
        serve.update(slots=4, max_len=128, page_size=8, paged_pages=40)
    cfg = get_preset(preset)
    tok = get_tokenizer(None)
    if cfg.vocab_size < tok.vocab_size:
        cfg = dataclasses.replace(cfg, vocab_size=512)
    params = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    print(f"weights on {dev.device_kind}", flush=True)
    doc = probe_prompt(doc_bytes)
    # (two questions that differ from their first byte on)
    asks = [probe_prompt(doc_bytes + q_bytes + 7 * i)[-q_bytes:][::-1 if i else 1]
            for i in range(2)]
    batcher = B.ContinuousBatcher(
        cfg, params, tok, batch_slots=serve["slots"],
        max_len=serve["max_len"], chunk_steps=serve["chunk_steps"],
        paged_pages=serve["paged_pages"], page_size=serve["page_size"],
        prefix_cache=True)

    def send(text, **kw):
        ids = list(tok.encode(text))
        rid = batcher.submit(ids, max_new_tokens=PROBE_TOKENS, **kw)
        toks = list(batcher.run()[rid])
        return {"prompt_tokens": len(ids), "tokens": toks, "ids": ids,
                "logprobs": [float(x) for x in batcher.result_logprobs[rid]],
                "cached_tokens": batcher.prefix_cached_tokens.get(rid, 0)}

    cold = send(doc + asks[0])
    hit = send(doc + asks[1])
    fresh = send(doc + asks[1], prefix_cache=False)
    tol = PREFIX_TOLS.get(a.config, PREFIX_TOLS["qwen2-7b-int8"])
    page = serve["page_size"]
    whole = (len(tok.encode(doc)) // page) * page
    report = {"config": a.config, "preset": preset, "tolerances": tol,
              "device_kind": dev.device_kind, "tree": ROOT,
              "cold": cold, "hit": hit, "fresh": fresh}
    ok = cold["cached_tokens"] == 0 and fresh["cached_tokens"] == 0 \
        and hit["cached_tokens"] >= whole - page
    report["hit_against_fresh"] = max(
        abs(x - y) for x, y in zip(hit["logprobs"], fresh["logprobs"]))
    report["tokens_equal"] = hit["tokens"] == fresh["tokens"]
    ok &= report["hit_against_fresh"] <= tol["hit_against_fresh"]
    with jax.default_matmul_precision("highest"):
        for rec in (hit, fresh) if hit["tokens"] != fresh["tokens"] else (hit,):
            t1 = time.time()
            seq = rec["ids"] + rec["tokens"][:-1]
            ref = np.asarray(dense_reference_logits(params, cfg, seq),
                             np.float32)[len(rec["ids"]) - 1:]
            rec["reference_logprobs"] = [
                float(jax.nn.log_softmax(jnp.asarray(r))[t])
                for r, t in zip(ref, rec["tokens"])]
            rec["reference_seconds"] = time.time() - t1
    fresh.setdefault("reference_logprobs", hit["reference_logprobs"])
    for rec in (hit, fresh):
        rec.pop("ids")
        rec["first_logprob_diff"] = abs(
            rec["logprobs"][0] - rec["reference_logprobs"][0])
        ok &= rec["first_logprob_diff"] <= tol["against_reference"]
    cold.pop("ids")
    took = {k[len("ops.dispatch."):]: int(v)
            for k, v in METRICS.snapshot()["counters"].items()
            if k.startswith("ops.dispatch.flash")}
    report["dispatch"], report["ok"] = took, bool(ok)
    print(json.dumps({k: report[k] for k in (
        "hit_against_fresh", "tokens_equal", "dispatch", "ok")}
        | {"cached_tokens": hit["cached_tokens"],
           "hit_first": hit["first_logprob_diff"],
           "fresh_first": fresh["first_logprob_diff"]}), flush=True)
    out = os.path.join(HERE, "chiprun_out", "reference_check")
    os.makedirs(out, exist_ok=True)
    name = a.config + (".prefix.json" if ROOT == HERE else ".prefix.tree.json")
    with open(os.path.join(out, name), "w") as f:
        json.dump(report, f, indent=1)
    print("reference_check:", "PASS" if ok else "FAIL", flush=True)
    return 0 if ok else 1


def _logprobs(logits, toks):
    return [float(jax.nn.log_softmax(jnp.asarray(row))[t])
            for row, t in zip(logits, toks)]


def _against(served, ref):
    d = np.abs(served - ref)
    return {"max_abs_logit_diff": float(d.max()),
            "mean_abs_logit_diff": float(d.mean())}


def _tie_to_batcher(row, served, theirs, their_logprobs, tol, apart) -> bool:
    """Tie the batcher's own tokens and logprobs on a probe to the as-served
    leg's logits ``served``, into ``row``; True where they are the ones
    those logits give.  Above 2,048 tokens (``apart``) an admission runs
    its FFNs in blocks and XLA compiles the batcher's program and the leg's
    apart (PR 34: the 6,000-byte probe's first logprob differs by 0.0025,
    later ones by what bfloat16 moves them, an expert's choice among them):
    there the batcher's logprobs are held to the REFERENCE's, at the
    as-served limit, and a greedy choice may part from the leg's where the
    leg's OWN logits hold the two tokens within that limit of each other
    (PR 45: K-EXAONE's probe parts at its fourth token, logprobs of -6.4 on
    a nearly flat distribution); behind the parting the two feed different
    tokens and nothing is compared."""
    mine = row["as_served"]
    row["batcher_tokens_equal"] = theirs == mine["tokens"]
    row["batcher_logprobs"] = their_logprobs
    same = next((j for j, (x, y) in enumerate(zip(theirs, mine["tokens"]))
                 if x != y), PROBE_TOKENS)
    tie = True
    if same < PROBE_TOKENS:
        row["batcher_parts_at"] = same
        row["batcher_parting_margin"] = float(
            served[same][mine["tokens"][same]] - served[same][theirs[same]])
        tie = apart and row["batcher_parting_margin"] <= tol["max_abs_logit"]
    row["batcher_logprob_max_abs_diff"] = max((
        abs(x - y) for x, y in zip(their_logprobs[:same],
                                   mine["served_logprobs"])), default=0.0)
    row["batcher_logprob_against_reference"] = max((
        abs(x - y) for x, y in zip(their_logprobs[:same],
                                   mine["reference_logprobs"])), default=0.0)
    tied = row["batcher_logprob_max_abs_diff"] < 1e-3 or (
        apart and row["batcher_logprob_against_reference"]
        <= tol["max_abs_logit"])
    return bool(tie and tied)


def retention_probes(a, config) -> int:
    """A model of power-retention layers (Brumby), which is served WITHOUT
    a pool; and a model of Mamba-2 layers beside attention layers
    (Nemotron-H, ``cfg.ssm_layers``), whose rows hold a float32 state BESIDE
    the page pool, through the same legs FROM its pool (the batcher's PAGED
    programs: ``admit_row_paged``'s prefill and splice, ``_decode_steps``'
    forward against the pool; a probe under one chunk of the scan, the
    1,500-byte one and the longest): for each of the
    configuration's probes the ``mechanism`` leg
    (float32 activations, the same programs, kernels and state) and the
    ``as_served`` leg against the reference's full forward over the same
    tokens, admission at the prompt's bucket (the chunked scan) and then 8
    recurrence steps in a slot of a cache shaped as the cell serves it;
    what a wrong model does to the reference on the second probe; the
    batcher's own tokens and logprobs tied to the as-served logits; and
    the control from the served path on EVERY probe and in BOTH legs: the
    state and its normaliser held in bfloat16 between the admission and
    each step (the precision below the configuration's), fed the leg's own
    tokens, whose worst reading over the probes' decode steps has to land
    outside the leg's limits, by one of them (a configuration whose as-served
    leg cannot tell one more rounding of the state says so in ``TOLS``,
    ``state_in_as_served`` False, with the readings: the control is then
    the mechanism leg's to fail, and reported in the other)."""
    from distributed_llms_tpu.core.observability import METRICS
    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.models.presets import get_preset
    from distributed_llms_tpu.ops import dispatch
    from distributed_llms_tpu.runtime import batcher as B
    from distributed_llms_tpu.runtime.shapes import bucket_length
    from distributed_llms_tpu.runtime.tokenizer import get_tokenizer

    serve, preset, TOL = dict(config["serve"]), config["preset"], TOLS[a.config]
    probe_bytes = tuple(serve["probe_bytes"])
    real = get_preset(preset)
    gdn = bool(real.gdn_layers)  # (Qwen3-Next: the delta-rule state)
    paged = bool(real.ssm_layers) or gdn
    if paged:  # under one chunk, the 1,500-byte one, the longest
        probe_bytes = (probe_bytes[0], probe_bytes[3], probe_bytes[-1])
    if a.rehearsal and paged:
        preset = "qwen3-next-tiny" if gdn else "nemotron3-super-tiny"
        probe_bytes = (5, 140, 300)
        serve.update(slots=4, max_len=512, page_size=16, paged_pages=100)
    elif a.rehearsal:
        preset, probe_bytes = "brumby-tiny", (5, 9, 33, 60, 140)
        serve.update(slots=4, max_len=256)
    cfg = get_preset(preset)
    state_fields = (("gdn_s",) if gdn else ("ssm_h",) if paged
                    else ("ret_s", "ret_z"))
    chunk = cfg.scan_chunk
    blk = serve["page_size"]
    ppr = serve["max_len"] // blk
    tok = get_tokenizer(None)
    if cfg.vocab_size < tok.vocab_size:  # as dlt-serve widens a tiny preset
        cfg = dataclasses.replace(cfg, vocab_size=512)
    t0 = time.time()
    params = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    print(f"weights on {dev.device_kind} after {time.time() - t0:.1f} s",
          flush=True)
    slots, s_len = serve["slots"], serve["max_len"]

    @functools.cache
    def programs(c):  # (one set a dtype: a leg and its control share it)
        @partial(jax.jit, donate_argnums=(1,))
        def admit(params, cache, prompt, plen, slot, page_list=None):
            logits, row, _ = B._prefill_row(
                model_lib.forward, params, c, kv_cache.row_dtype(cache),
                s_len, prompt, plen)
            if page_list is not None:  # the pool, and the state beside it
                return (kv_cache.write_row(cache, page_list, row, slot),
                        logits[0, 0])
            return B._splice_row(cache, slot, row), logits[0, 0]

        @partial(jax.jit, donate_argnums=(1,))
        def step(params, cache, last_tok, real_lens, active, tables=None):
            mask = (jnp.arange(s_len)[None, :] <= real_lens[:, None])
            how = ({"attn_mask": mask[:, None, None, :]} if tables is None
                   else {"kv_tables": tables})
            logits, cache = model_lib.forward(
                params, c if tables is None else dataclasses.replace(
                    c, ragged_decode=dispatch.attention_mode() != "fallback"),
                last_tok[:, None], positions=real_lens[:, None],
                cache=cache, cache_index=real_lens,
                seq_lens=active.astype(jnp.int32), **how)
            return logits[:, 0], cache

        @partial(jax.jit, donate_argnums=(0,))
        def to_bf16(cache):  # the state as bfloat16 would hold it (a cast
            # there and back is dropped on the TPU: excess precision is
            # allowed by default)
            return dataclasses.replace(cache, **{
                f: jax.lax.reduce_precision(getattr(cache, f), 8, 7)
                for f in state_fields})

        return admit, step, to_bf16

    def served_logits(dtype, ids, force=None, state_bf16=False):
        """[8, V] logits of the served path with ``dtype`` activations:
        positions len(ids)-1 .. +7, each step fed the greedy token or the
        tokens ``force`` names."""
        c = dataclasses.replace(cfg, dtype=dtype)
        admit, step, to_bf16 = programs(c)
        plen, bucket = len(ids), bucket_length(len(ids))
        prompt = np.zeros((bucket,), np.int32)
        prompt[:plen] = ids
        where = {}
        if paged:
            n_pages = -(-(plen + PROBE_TOKENS) // blk)
            page_list = np.zeros((ppr,), np.int32)
            page_list[:n_pages] = 1 + np.arange(n_pages)
            # (float32 activations double an 8,192-bucket admission's
            # temporaries, 3.8 GB beside 13 GB resident: that leg keeps 8
            # slots' states, the probe's among them, and a pool of one
            # row's pages, three times what the longest probe fills)
            wide = dtype == "float32" and slots > 8
            slots_here = 8 if wide else slots
            tables = np.zeros((slots_here, ppr), np.int32)
            tables[a.slot] = page_list
            cache = kv_cache.make_pool(
                c, ppr + 1 if wide else serve["paged_pages"], blk,
                slots=slots_here)
            cache, first = admit(
                params, cache, jnp.asarray(prompt), jnp.int32(plen),
                jnp.int32(a.slot), jnp.asarray(page_list))
            where = {"tables": jnp.asarray(tables)}
        else:
            cache = kv_cache.init_cache(c, slots, s_len)
            cache, first = admit(params, cache, jnp.asarray(prompt),
                                 jnp.int32(plen), jnp.int32(a.slot))
        logits = [np.asarray(first, np.float32)]
        toks = [int(np.argmax(logits[0])) if force is None else force[0]]
        rows = getattr(cache, state_fields[0]).shape[1] if paged else slots
        active = np.zeros((rows,), bool)
        active[a.slot] = True
        last = np.zeros((rows,), np.int32)
        lens = np.zeros((rows,), np.int32)
        for j in range(PROBE_TOKENS - 1):
            if state_bf16:
                cache = to_bf16(cache)
            last[a.slot], lens[a.slot] = toks[-1], plen + j
            lg, cache = step(params, cache, jnp.asarray(last),
                             jnp.asarray(lens), jnp.asarray(active), **where)
            logits.append(np.asarray(lg[a.slot], np.float32))
            toks.append(int(np.argmax(logits[-1])) if force is None
                        else force[j + 1])
        return np.stack(logits), toks

    logprobs, against = _logprobs, _against

    def inside(got, leg):
        return bool(
            got["max_abs_logit_diff"] <= TOL[leg]["max_abs_logit"]
            and got["mean_abs_logit_diff"] <= TOL[leg]["mean_abs_logit"])

    report = {"config": a.config, "preset": preset, "tolerances": TOL,
              "device_kind": dev.device_kind, "probes": []}
    ok = True
    for n in probe_bytes[:a.probes]:
        ids = list(tok.encode(probe_prompt(n)))
        plen = len(ids)
        row = {"bytes": n, "prompt_tokens": plen,
               "bucket": bucket_length(plen),
               "chunks": -(-plen // chunk)}
        for leg, dtype in (("mechanism", "float32"), ("as_served", cfg.dtype)):
            precision = "highest" if leg == "mechanism" else "default"
            with jax.default_matmul_precision(precision):
                served, toks = served_logits(dtype, ids)
            print(f"  probe {n} {leg}: served", flush=True)
            t1 = time.time()
            ref = np.asarray(reference_logits(params, cfg, ids + toks[:-1]),
                             np.float32)[plen - 1: plen - 1 + PROBE_TOKENS]
            got = against(served, ref)
            got.update(
                tokens=toks, logit_rms=float(np.sqrt((ref ** 2).mean())),
                reference_argmax_equal=[int(np.argmax(r)) for r in ref] == toks,
                served_logprobs=logprobs(served, toks),
                reference_logprobs=logprobs(ref, toks),
                reference_seconds=time.time() - t1)
            got["first_logprob_diff"] = abs(
                got["served_logprobs"][0] - got["reference_logprobs"][0])
            got["within_tolerances"] = inside(got, leg)
            row[leg] = got
            # The control, in this leg: the same tokens, the same reference
            # logits, the state held in bfloat16 between the admission and
            # every step (the decode steps' logits alone pass through it).
            with jax.default_matmul_precision(precision):
                lower, _ = served_logits(dtype, ids, force=toks,
                                         state_bf16=True)
            key = "state_bf16" + ("_mechanism" if leg == "mechanism" else "")
            row[key] = against(lower, ref)
            row[key]["decode_steps"] = against(lower[1:], ref[1:])
            row[key]["sound_decode_steps"] = against(served[1:], ref[1:])
            row[key]["max_abs_logprob_shift"] = max(
                abs(x - y) for x, y in zip(
                    logprobs(lower, toks), got["served_logprobs"]))
        if n == probe_bytes[1]:
            wrongs = {"degree_3": {"degree": 3}, "no_gate": {"gated": False},
                      "no_qk_norm": {"qk_norm": False},
                      "no_rope": {"rope": False},
                      "int4_weights": {"int4": True}}
            if gdn:
                wrongs = {
                    "nine_picks_for_ten": {"num_experts_per_token":
                                           cfg.num_experts_per_token - 1},
                    "no_attention_gate": {"attn_gate": False},
                    "no_shared_gate": {"shared_gate": False},
                    "beta_one": {"beta_one": True},
                    "gate_before_norm": {"gate_first": True},
                    "int4_weights": {"int4": True}}
            elif paged:
                wrongs = {
                    "one_expert_fewer": {"num_experts_per_token":
                                         cfg.num_experts_per_token - 1},
                    "silu_experts": {"expert_act": "silu"},
                    "no_latent": {"latent": False},
                    "norm_before_gate": {"gate_first": False},
                    "no_conv_bias": {"conv_bias": False},
                    "int4_weights": {"int4": True}}
            row["wrong"] = {
                name: against(np.asarray(reference_logits(
                    params, cfg, ids + toks[:-1], **changed),
                    np.float32)[plen - 1: plen - 1 + PROBE_TOKENS], ref)
                for name, changed in wrongs.items()}
        # The batcher's own programs on the same probe, sent alone.
        batcher = B.ContinuousBatcher(
            cfg, params, tok, batch_slots=slots, max_len=s_len,
            chunk_steps=serve["chunk_steps"], **(dict(
                paged_pages=serve["paged_pages"], page_size=blk)
                if paged else {}))
        rid = batcher.submit(ids, max_new_tokens=PROBE_TOKENS)
        out = batcher.run()
        tied = _tie_to_batcher(
            row, served, list(out[rid]),
            [float(x) for x in batcher.result_logprobs[rid]],
            TOL["as_served"], row["bucket"] > model_lib._TOKEN_BLOCK)
        del batcher
        good = (row["mechanism"]["within_tolerances"]
                and row["as_served"]["within_tolerances"] and tied)
        row["ok"] = bool(good)
        ok &= good or a.rehearsal
        report["probes"].append(row)
        print(json.dumps({k: ({x: y for x, y in v.items()
                               if not x.endswith("logprobs")}
                              if isinstance(v, dict) else v)
                          for k, v in row.items()
                          if k != "batcher_logprobs"}), flush=True)
    # The control's worst readings against the as-served leg's limits and
    # its worst readings: outside by one of the limits, not by each.
    worst = lambda leg, k: max(p[leg][k] for p in report["probes"])
    for leg, key in (("as_served", "state_bf16"),
                     ("mechanism", "state_bf16_mechanism")):
        control = {k: worst(key, k) for k in (
            "max_abs_logit_diff", "mean_abs_logit_diff",
            "max_abs_logprob_shift")}
        control["sound_at_most"] = {k: worst(leg, k) for k in (
            "max_abs_logit_diff", "mean_abs_logit_diff")}
        control["limits"] = TOL[leg]
        control["outside_tolerances"] = not inside(control, leg)
        # (a leg whose own roundings drown the state's says so in TOLS, with
        # its readings: the control is then the mechanism leg's to fail)
        control["required"] = (leg == "mechanism"
                               or TOL.get("state_in_as_served", True))
        report[key] = control
        ok &= (control["outside_tolerances"] or not control["required"]
               or a.rehearsal)
        print(json.dumps({key: control}), flush=True)
    report["golden_from_reference"] = all(
        p["as_served"]["first_logprob_diff"] <= GOLDEN_FROM_REFERENCE
        for p in report["probes"])
    report["dispatch"] = {k: v for k, v in
                          METRICS.snapshot()["counters"].items()
                          if k.startswith("ops.dispatch.")}
    stats = dev.memory_stats() or {}
    report["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    out_dir = os.path.join(ROOT, "chiprun_out", "reference_check")
    os.makedirs(out_dir, exist_ok=True)
    name = a.config + (".rehearsal" if a.rehearsal else "")
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"ok": bool(ok),
                      "golden_from_reference": report["golden_from_reference"],
                      "dispatch": report["dispatch"],
                      "memory_peak_bytes": report["memory_peak_bytes"]}))
    print("reference_check:", "PASS" if ok else "FAIL", flush=True)
    return 0 if ok else 1


def served_programs(cfg, cfg_decode):
    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.runtime import batcher as B

    @partial(jax.jit, donate_argnums=(1,))
    def admit(params, cache, page_list, prompt, plen, slot):
        logits, row, _ = B._prefill_row(
            model_lib.forward, params, cfg, kv_cache.row_dtype(cache),
            page_list.shape[0] * cache.k.shape[2], prompt, plen)
        cache, tok, lp = B._paged_splice(
            cache, page_list, row, logits, jax.random.key(0), 0.0, 0, 1.0,
            slot=slot)
        # (the admission's head reads the last real position alone)
        return cache, logits[0, 0], tok, lp

    @partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, last_tok, real_lens, active, tables):
        logits, cache = model_lib.forward(
            params, cfg_decode, last_tok[:, None],
            positions=real_lens[:, None], cache=cache, cache_index=real_lens,
            kv_tables=tables, seq_lens=active.astype(jnp.int32))
        return logits[:, 0], cache

    return admit, step


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="lfm2-8b-a1b-int8")
    ap.add_argument("--rehearsal", action="store_true",
                    help="lfm2-tiny on the CPU at a tiny shape: the same "
                         "code, no number that means anything")
    ap.add_argument("--tree", default=None,
                    help="serve another checkout's programs (a parent "
                         "under _chip/); read before the imports")
    ap.add_argument("--slot", type=int, default=3)
    ap.add_argument("--probes", type=int, default=None,
                    help="how many of the probes to run, from the first "
                         "(the controls ride on the first; fewer than all "
                         "of them writes no golden worth keeping)")
    a = ap.parse_args()

    from distributed_llms_tpu.models import kv_cache, model as model_lib
    from distributed_llms_tpu.models.presets import get_preset
    from distributed_llms_tpu.ops import dispatch
    from distributed_llms_tpu.runtime import batcher as B
    from distributed_llms_tpu.runtime.shapes import bucket_length
    from distributed_llms_tpu.runtime.tokenizer import get_tokenizer

    with open(os.path.join(ROOT, "benchmark", "configs",
                           a.config + ".json")) as f:
        config = json.load(f)
    if a.config in PREFIX_TOLS:  # a dense model: the prefix cache's probes
        return prefix_cache_probes(a, config)
    served = get_preset(config["preset"])
    if served.ret_layers or served.ssm_layers or served.gdn_layers:  # a
        # state a row: with no pool, or beside one
        return retention_probes(a, config)
    serve = dict(config["serve"])
    preset, probe_bytes = config["preset"], PROBE_BYTES
    TOL = TOLS[a.config]
    if a.rehearsal:
        preset = next((tiny for tiny in ("ax-k1-tiny", "k-exaone-tiny",
                                         "smallthinker-tiny")
                       if preset.startswith(tiny[:-5])), "lfm2-tiny")
        probe_bytes = (5, 9, 33, 60)
        serve.update(slots=4, max_len=128, page_size=8, paged_pages=40)
    cfg = get_preset(preset)
    if cfg.swa_layers:
        probe_bytes += (100 if a.rehearsal else LONG_PROBE,)
        if cfg.sliding_window > max(PROBE_BYTES) + PROBE_TOKENS:
            # No other probe's decode steps cross the window: this one's
            # tokens (BOS and window - 4 bytes) end three short of it.
            probe_bytes += (cfg.sliding_window - 4,)
    tok = get_tokenizer(None)
    if cfg.vocab_size < tok.vocab_size:  # as dlt-serve widens a tiny preset
        cfg = dataclasses.replace(cfg, vocab_size=512)
    t0 = time.time()
    params = model_lib.init_params_quantized(jax.random.key(0), cfg, 8)
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    print(f"weights on {dev.device_kind} after {time.time() - t0:.1f} s",
          flush=True)

    slots, blk = serve["slots"], serve["page_size"]
    ppr = serve["max_len"] // blk
    kernels = dispatch.attention_mode() != "fallback"

    def make_batcher():
        # One a probe, dropped after it: its pool and the legs' own would
        # not fit side by side beside A.X-K1's weights.
        return B.ContinuousBatcher(
            cfg, params, tok, batch_slots=slots, max_len=serve["max_len"],
            chunk_steps=serve["chunk_steps"],
            paged_pages=serve["paged_pages"], page_size=blk)

    def served_logits(dtype, ids, force=None, slots=slots):
        """[8, V] logits of the served path with ``dtype`` activations:
        positions len(ids)-1 .. +7, each decode step fed the greedy token
        (so both dtypes and the reference may see other tokens after the
        first; the reference is run on each's own), or the tokens
        ``force`` names."""
        c = dataclasses.replace(cfg, dtype=dtype)
        admit, step = served_programs(
            c, dataclasses.replace(c, ragged_decode=kernels))
        plen, bucket = len(ids), bucket_length(len(ids))
        n_pages = -(-(plen + PROBE_TOKENS) // blk)
        page_list = np.zeros((ppr,), np.int32)
        page_list[:n_pages] = 1 + np.arange(n_pages)
        prompt = np.zeros((bucket,), np.int32)
        prompt[:plen] = ids
        # (float32 pages of A.X-K1's latent pool would be 4.6 GB beside
        # 9.7 GB of weights: that leg's pool holds a quarter of the pages,
        # forty times what a probe fills)
        # (and K-EXAONE's float32 pages 5.8 GB beside 9.5: an eighth, five
        # times what the long probe fills)
        pages = max(ppr + 1, serve["paged_pages"] // (
            1 if dtype != "float32" else 4 if c.kv_lora_rank
            else 8 if c.swa_layers else 1))
        # (and SmallThinker's float32 rings 4.8 GB at 32 slots beside 6.5:
        # that leg keeps 8 slots, the probe's among them)
        big_rings = dtype == "float32" and len(c.swa_layers) * (
            c.sliding_window or 0) * slots > 1 << 19
        slots = min(slots, 8) if big_rings else slots
        cache = kv_cache.make_pool(c, pages, blk, slots=slots)
        cache, first, tok0, _ = admit(
            params, cache, jnp.asarray(page_list), jnp.asarray(prompt),
            jnp.int32(plen), jnp.int32(a.slot))
        logits = [np.asarray(first, np.float32)]
        toks = [int(tok0) if force is None else force[0]]
        tables = np.zeros((slots, ppr), np.int32)
        tables[a.slot] = page_list
        active = np.zeros((slots,), bool)
        active[a.slot] = True
        last = np.zeros((slots,), np.int32)
        lens = np.zeros((slots,), np.int32)
        for j in range(PROBE_TOKENS - 1):
            last[a.slot], lens[a.slot] = toks[-1], plen + j
            lg, cache = step(params, cache, jnp.asarray(last),
                             jnp.asarray(lens), jnp.asarray(active),
                             jnp.asarray(tables))
            logits.append(np.asarray(lg[a.slot], np.float32))
            toks.append(int(np.argmax(logits[-1])) if force is None
                        else force[j + 1])
        return np.stack(logits), toks

    logprobs, against = _logprobs, _against

    report = {"config": a.config, "preset": preset, "tolerances": TOL,
              "device_kind": dev.device_kind, "probes": []}
    ok = True
    for n in probe_bytes[:a.probes]:
        ids = list(tok.encode(probe_prompt(n)))
        plen = len(ids)
        row = {"bytes": n, "prompt_tokens": plen,
               "bucket": bucket_length(plen)}
        for leg, dtype in (("mechanism", "float32"), ("as_served", cfg.dtype)):
            with jax.default_matmul_precision(
                    "highest" if leg == "mechanism" else "default"):
                served, toks = served_logits(dtype, ids)
            print(f"  probe {n} {leg}: served", flush=True)
            t1 = time.time()
            ref = np.asarray(reference_logits(params, cfg, ids + toks[:-1]),
                             np.float32)[plen - 1: plen - 1 + PROBE_TOKENS]
            got = against(served, ref)
            got.update(
                tokens=toks, logit_rms=float(np.sqrt((ref ** 2).mean())),
                reference_argmax_equal=[int(np.argmax(r)) for r in ref] == toks,
                served_logprobs=logprobs(served, toks),
                reference_logprobs=logprobs(ref, toks),
                reference_seconds=time.time() - t1)
            got["first_logprob_diff"] = abs(
                got["served_logprobs"][0] - got["reference_logprobs"][0])
            got["within_tolerances"] = bool(
                got["max_abs_logit_diff"] <= TOL[leg]["max_abs_logit"]
                and got["mean_abs_logit_diff"] <= TOL[leg]["mean_abs_logit"])
            row[leg] = got
        if n == probe_bytes[0]:
            # (of the as_served leg, for the control at the end)
            control = (ids, toks, ref, got["served_logprobs"])
        # (a wrong window shows on a probe longer than the window: the second)
        if n == probe_bytes[1 if cfg.swa_layers else 0]:
            # What a wrong model does to the same probe's reference logits:
            # each has to land outside the tolerances, or they guard nothing.
            wrongs = {
                "three_experts": {"num_experts_per_token":
                                  cfg.num_experts_per_token - 1},
                "no_norm_topk": {"norm_topk_prob": False},
                "int4_weights": {"int4": True},
            }
            if cfg.moe_n_group > 1:
                wrongs["no_groups"] = {"n_group": 1, "topk_group": 1}
            if cfg.swa_layers:
                wrongs["full_rope"] = {"full_rope": True}
            if cfg.swa_layers and cfg.qk_norm:
                wrongs["no_qk_norm"] = {"qk_norm": False}
            if cfg.swa_layers and plen > cfg.sliding_window:
                wrongs["window_x2"] = {
                    "sliding_window": 2 * cfg.sliding_window}
            if cfg.moe_router_input == "block_input":
                wrongs.update(router_on_ffn_norm={"router_input": "ffn_norm"},
                              silu_gate={"gate_act": "silu"})
            row["wrong"] = {
                name: against(np.asarray(reference_logits(
                    params, cfg, ids + toks[:-1], **changed),
                    np.float32)[plen - 1: plen - 1 + PROBE_TOKENS], ref)
                for name, changed in wrongs.items()}

        # The batcher's own programs on the same probe, sent alone: their
        # tokens and logprobs are the ones the as-served logits give.
        batcher = make_batcher()
        rid = batcher.submit(ids, max_new_tokens=PROBE_TOKENS)
        out = batcher.run()
        mine = row["as_served"]
        tied = _tie_to_batcher(
            row, served, list(out[rid]),
            [float(x) for x in batcher.result_logprobs[rid]],
            TOL["as_served"], row["bucket"] > model_lib._TOKEN_BLOCK)
        del batcher
        good = (row["mechanism"]["within_tolerances"]
                and mine["within_tolerances"] and tied)
        row["ok"] = bool(good)
        ok &= good or a.rehearsal
        report["probes"].append(row)
        print(json.dumps({k: ({x: y for x, y in v.items()
                               if not x.endswith("logprobs")}
                              if isinstance(v, dict) else v)
                          for k, v in row.items()
                          if k != "batcher_logprobs"}), flush=True)
    # The control from the served path (the batcher above is done with the
    # weights: the expert stacks change where they lie).
    from distributed_llms_tpu.checkpoint.quantize import QuantizedTensor

    ids, toks, ref, sound = control
    experts = params["blocks"]["moe"]["experts"]
    for name, w in experts.items():
        if isinstance(w, QuantizedTensor):
            experts[name] = dataclasses.replace(
                w, data=_int8_on_int4_grid(w.data))
    served, _ = served_logits(cfg.dtype, ids, force=toks)
    got = against(served, ref)
    got["outside_as_served_tolerances"] = bool(
        got["max_abs_logit_diff"] > TOL["as_served"]["max_abs_logit"]
        or got["mean_abs_logit_diff"] > TOL["as_served"]["mean_abs_logit"])
    # What the benchmark's ``correct`` would see: the probe's logprobs
    # against those the sound programs gave for the same tokens (its limit
    # is 0.05, benchmark/run.py GOLDEN_TOL).
    got["max_abs_logprob_shift"] = max(
        abs(x - y) for x, y in zip(logprobs(served, toks), sound))
    report["served_int4_experts"] = got
    ok &= got["outside_as_served_tolerances"] or a.rehearsal
    print(json.dumps({"served_int4_experts": got}), flush=True)
    report["golden_from_reference"] = all(
        p["as_served"]["first_logprob_diff"] <= GOLDEN_FROM_REFERENCE
        for p in report["probes"])

    from distributed_llms_tpu.core.observability import METRICS

    report["dispatch"] = {k: v for k, v in
                          METRICS.snapshot()["counters"].items()
                          if k.startswith("ops.dispatch.")}
    stats = dev.memory_stats() or {}
    report["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    out_dir = os.path.join(ROOT, "chiprun_out", "reference_check")
    os.makedirs(out_dir, exist_ok=True)
    name = a.config + (".rehearsal" if a.rehearsal else "")
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(report, f, indent=1)
    # The benchmark's golden: the reference's logprobs of the served tokens
    # where the served first-token logprob is within half the golden's
    # tolerance of them on every probe, the served path's own otherwise
    # (with the reference's beside them).
    source = "reference" if report["golden_from_reference"] else "batcher"
    with open(os.path.join(out_dir, name + ".golden.json"), "w") as f:
        json.dump({
            "device_kind": dev.device_kind, "tolerance": 0.05,
            "from": source,
            "probes": [
                {"bytes": p["bytes"], "logprobs": [round(x, 6) for x in (
                    p["as_served"]["reference_logprobs"]
                    if source == "reference" else p["batcher_logprobs"])]}
                for p in report["probes"][:len(PROBE_BYTES)]],
            "reference": [
                {"bytes": p["bytes"],
                 "logprobs": [round(x, 6) for x in
                              p["as_served"]["reference_logprobs"]],
                 "first_logprob_diff": p["as_served"]["first_logprob_diff"],
                 "max_abs_logit_diff": p["as_served"]["max_abs_logit_diff"],
                 "mean_abs_logit_diff": p["as_served"]["mean_abs_logit_diff"]}
                for p in report["probes"]],
        }, f, indent=1)
    print(json.dumps({"ok": bool(ok),
                      "golden_from_reference": report["golden_from_reference"],
                      "dispatch": report["dispatch"],
                      "memory_peak_bytes": report["memory_peak_bytes"]}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
