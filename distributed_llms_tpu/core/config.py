"""Typed configuration system.

The reference hard-codes every knob (ports at src/master/node.py:15 and
src/worker/node.py:35, model id and shard count at run_master.py:17, heartbeat
period at src/worker/node.py:273, timeouts at src/master/node.py:117 and
src/network/protocol.py:77) and its planned YAML/JSON config system
(plan.md:70-73) never landed.  Here every knob lives in one typed dataclass
tree, loadable from JSON/YAML files or CLI-style ``key=value`` overrides.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any

try:  # yaml is available in the image; gate anyway.
    import yaml

    _HAVE_YAML = True
except Exception:  # pragma: no cover
    _HAVE_YAML = False


_ATTN_IMPLS = {"dot", "ring", "flash", "ulysses"}


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for a decoder-only transformer.

    One dataclass covers every supported family (GPT-2, OPT, TinyLlama,
    Llama-2, Llama-3, Mixtral); ``family`` selects the block flavour
    (LayerNorm+learned-pos vs RMSNorm+RoPE+GQA).  "opt" is the gpt2 layout
    with separate q/k/v projections folded in conversion, a ReLU MLP, and
    HF OPT's position-table offset of 2 — the reference's own default model
    (run_master.py:17, facebook/opt-125m).
    """

    family: str = "gpt2"  # "gpt2" | "opt" | "llama" | "neox" | "hybrid"
    #   ("hybrid": layers stacked by KIND and walked in ``layer_types``'
    #   order, models.model.run_layers: LFM2's convolutions beside GQA,
    #   A.X-K1's latent attention, Brumby's power retention)
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 12  # < num_heads => grouped-query attention
    head_dim: int | None = None  # default hidden_size // num_heads
    max_seq_len: int = 1024
    rope_theta: float = 10000.0
    # Llama-3.1-style rope scaling ("rope_type": "llama3"): piecewise
    # frequency rescale that stretches low-frequency (long-wavelength)
    # components by `factor` while keeping high-frequency ones, with a
    # smooth ramp between — how 3.1/3.2 extend 8k-trained RoPE to 128k.
    # factor == 1.0 disables (plain RoPE).  ``rope_scaling_type`` "yarn"
    # reads the same factor and original length by YaRN's rule instead
    # (layers.rope_frequencies, the fields further down); the converter
    # still rejects every HF rope_type but llama3 (linear, dynamic, yarn,
    # longrope): no checkpoint of a yarn model is converted yet.
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_len: int = 8192
    # GPT-NeoX/Pythia: rotate only the first rotary_pct of each head's dims
    # (partial rotary); the rest pass through position-free.
    rotary_pct: float = 1.0
    # GPT-NeoX/Pythia parallel residual: x + attn(ln1 x) + mlp(ln2 x)
    # (HF use_parallel_residual; False = sequential pre-LN like GPT-2).
    parallel_residual: bool = False
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    # MLP activation for the gpt2-layout families ("gelu" for GPT-2, "relu"
    # for OPT); the llama family is SwiGLU regardless.
    activation: str = "gelu"
    # Attention implementation: "dot" (XLA-fused), "flash" (Pallas fused
    # blockwise kernel, ops/flash.py, for a forward with NO cache: training
    # and eval — note the backward recomputes attention densely at O(T^2)
    # memory; a prefill into a cache takes the kernel by what the call can
    # see, whatever this says: models.model._self_attention,
    # _continuation_attention), "ring" (sequence-parallel
    # ppermute ring over the 'seq' mesh axis; prefill/training only), or
    # "ulysses" (sequence-parallel all-to-all head scatter over 'seq';
    # needs num_heads and num_kv_heads divisible by the seq axis).
    attn_impl: str = "dot"
    # Llama-layout blocks with q/k/v projection biases (Qwen2's one
    # architectural delta from Llama); gpt2/opt layouts always carry theirs.
    qkv_bias: bool = False
    # Gated-MLP activation: "silu" (Llama/Qwen2), "gelu_tanh" (Gemma's
    # GeGLU) or "relu" (a ReGLU).  The dense MLP, a shared expert and the
    # routed experts of either expert layer all take it (layers.gate_fn).
    # "relu2": NO gate matrix, ``relu(x W_up)^2 W_down`` (the latent
    # experts and their shared expert, ``moe_latent_size``).
    gate_act: str = "silu"
    # Embedding multiplier applied after lookup (Gemma: sqrt(hidden_size)).
    embed_scale: float = 1.0
    # CONVERTER-ONLY flag: the checkpoint's RMSNorm computes with
    # (1 + weight) (Gemma); convert folds the +1 into the stored scales so
    # the runtime rms_norm stays unchanged.  Random init (ones) is already
    # the folded identity.
    norm_plus_one: bool = False
    # Ragged single-token decode attention (ops/decode_attn.py): row b reads
    # only its cache prefix [0, cache_index[b]] instead of the full width S.
    # Opt-in CONTRACT flag, not just a speed knob: setting it asserts the
    # caller's attn_mask on the per-row-cache_index decode path is exactly
    # that prefix mask (the ContinuousBatcher's is; arbitrary masks are not).
    ragged_decode: bool = False
    # Sliding-window attention (Mistral): query at position p attends keys in
    # (p - window, p].  None = global causal.  Enforced via masks on the dot
    # paths; the flash and ragged-decode kernels carry the window natively
    # (flash skips out-of-window tiles without DMAing them; ragged decode
    # reads only each row's window span).  The paged decode kernel and the
    # seq-parallel impls reject it (full-prefix / global-causal by
    # construction).  The KV cache keeps max_seq_len slots (no rolling
    # buffer yet) — masking is what bounds the attention span, not cache
    # size.
    # A hybrid model (``layer_types``) gives the window to its "swa" layers
    # alone: its "attn" layers attend the whole prefix, and the served
    # (paged) path keeps a swa layer's last ``sliding_window`` keys and
    # values a row in a ring beside the pool (models/kv_cache.py).
    sliding_window: int | None = None

    def __post_init__(self):
        # A list from a JSON file: the config is a static jit argument.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.attn_impl not in _ATTN_IMPLS:
            raise ValueError(
                f"unknown attn_impl {self.attn_impl!r}; choose from {sorted(_ATTN_IMPLS)}"
            )
        if self.gate_act not in ("silu", "gelu_tanh", "relu", "relu2"):
            raise ValueError(
                f"unknown gate_act {self.gate_act!r}; choose silu, gelu_tanh, "
                "relu or relu2"
            )
        object.__setattr__(self, "no_ffn_layers", tuple(self.no_ffn_layers))
        if self.moe_router_input not in ("ffn_norm", "block_input"):
            raise ValueError(
                f"unknown moe_router_input {self.moe_router_input!r}; choose "
                "ffn_norm or block_input"
            )
        if self.moe_router_input == "block_input" and (
                self.family != "hybrid" or not self.num_experts):
            raise ValueError(
                "moe_router_input='block_input' is the hybrid family's "
                "expert layers' (models.model.run_layers)"
            )
        if self.moe_score_fn not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown moe_score_fn {self.moe_score_fn!r}; choose softmax "
                "or sigmoid"
            )
        if (self.family == "hybrid") != bool(self.layer_types):
            raise ValueError(
                "layer_types is the hybrid family's layer pattern: give both "
                "or neither"
            )
        if self.family == "hybrid" and self.num_experts and self.moe_capacity:
            raise ValueError(
                "the hybrid family routes without a capacity rule "
                "(layers.moe_dropless): set moe_capacity=False"
            )
        if self.layer_types:
            bad = set(self.layer_types) - {"conv", "attn", "mla", "swa",
                                           "ret", "ssm", "gdn"}
            if bad or len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types must name {self.num_layers} layers as "
                    f"'conv', 'attn', 'swa', 'mla', 'ret', 'ssm' or 'gdn', "
                    f"got {self.layer_types!r}"
                )
        if self.no_ffn_layers and (
                not self.layer_types
                or not set(self.no_ffn_layers) <= set(range(self.num_layers))):
            raise ValueError(
                f"no_ffn_layers {self.no_ffn_layers!r} names blocks of a "
                f"hybrid model's {self.num_layers}"
            )
        if "ssm" in self.layer_types and (
                set(self.layer_types) - {"ssm", "attn"}
                or (self.ssm_heads * self.ssm_head_dim) % 128
                or 128 % self.ssm_head_dim
                or self.ssm_heads % self.ssm_groups
                or (self.ssm_heads // self.ssm_groups)
                % (128 // self.ssm_head_dim)):
            raise ValueError(
                "a model of state-space layers mixes 'ssm' with 'attn' "
                "alone, and its heads lie 128 // ssm_head_dim to a 128-lane "
                "row, each row inside ONE group (the state's layout, "
                "ops/ssm.py)"
            )
        if "gdn" in self.layer_types and (
                set(self.layer_types) - {"gdn", "attn"}
                or self.gdn_key_dim % 128 or self.gdn_value_dim % 128
                or not self.gdn_key_heads
                or self.gdn_value_heads % self.gdn_key_heads
                or self.gdn_chunk % 16
                or self.gdn_chunk & (self.gdn_chunk - 1)):
            raise ValueError(
                "a model of gated delta-rule layers mixes 'gdn' with 'attn' "
                "alone; its key and value heads are whole 128-lane tiles, "
                "each key head read by gdn_value_heads // gdn_key_heads "
                "value heads, and a chunk of its scan is a power of two of "
                "16 tokens or more (the triangle's blocks, ops/gdn.py)"
            )
        if (self.attn_out_gate or self.moe_shared_gate) and (
                set(self.layer_types) - {"gdn", "ssm", "conv", "attn"}
                or not self.layer_types):
            raise ValueError(
                "attn_out_gate and moe_shared_gate are the hybrid family's, "
                "on its 'attn' layers (models.model._attention) and its "
                "shared expert (models.model.run_layers)"
            )
        if (self.gate_act == "relu2") != bool(self.moe_latent_size):
            raise ValueError(
                "gate_act 'relu2' is the non-gated expert's, which works in "
                "a latent (moe_latent_size): give both or neither"
            )
        if "ret" in self.layer_types and (
                set(self.layer_types) != {"ret"} or self.head_dim_ != 128
                or not self.qk_norm or self.num_experts):
            raise ValueError(
                "power retention is every layer's or none's: layer_types "
                "all 'ret', heads of 128 (the state's 65 x 128 x 128 "
                "layout, ops/retention.py), qk_norm, no experts"
            )
        if ("swa" in self.layer_types) != (
                bool(self.layer_types) and self.sliding_window is not None):
            raise ValueError(
                "a hybrid model's sliding_window is the window of its 'swa' "
                "layers: give both or neither"
            )
        if ("mla" in self.layer_types) != (self.kv_lora_rank > 0) or (
                self.kv_lora_rank and set(self.layer_types) != {"mla"}):
            raise ValueError(
                "latent attention is every layer's or none's: layer_types "
                "all 'mla' together with kv_lora_rank, q_lora_rank and the "
                "three head sizes"
            )
        if self.rope_scaling_type not in ("llama3", "yarn"):
            raise ValueError(
                f"unknown rope_scaling_type {self.rope_scaling_type!r}; "
                "choose llama3 or yarn"
            )
        if self.num_experts and (
                self.num_experts % self.moe_n_group
                or not 1 <= self.moe_topk_group <= self.moe_n_group
                or self.num_experts_per_token > self.moe_topk_group
                * (self.num_experts // self.moe_n_group)):
            raise ValueError(
                f"{self.num_experts} experts do not split into "
                f"{self.moe_n_group} groups of which {self.moe_topk_group} "
                f"hold {self.num_experts_per_token} choices"
            )
        if self.experts_held is not None and (
                self.moe_capacity or self.experts_offset < 0
                or not 0 < self.experts_held
                <= self.num_experts - self.experts_offset):
            raise ValueError(
                f"experts_held {self.experts_held} at offset "
                f"{self.experts_offset} is no run of the {self.num_experts} "
                "experts of a model routed without a capacity rule"
            )
        if not 0.0 < self.rotary_pct <= 1.0:
            raise ValueError(
                f"rotary_pct must be in (0, 1], got {self.rotary_pct}"
            )
        if self.rotary_pct < 1.0:
            rot = int(self.head_dim_ * self.rotary_pct)
            if rot < 2 or rot % 2:
                raise ValueError(
                    f"rotary_pct {self.rotary_pct} of head_dim "
                    f"{self.head_dim_} gives {rot} rotary dims; need an "
                    "even count >= 2"
                )
        if self.sliding_window is not None:
            if self.sliding_window < 1:
                raise ValueError(
                    f"sliding_window must be >= 1, got {self.sliding_window}"
                )
            if self.attn_impl in ("ring", "ulysses"):
                # The seq-parallel impls attend the full (causal) global
                # sequence; silently ignoring the window would be wrong
                # numerics for any prompt longer than it.
                raise ValueError(
                    "sliding_window is not supported with ring/ulysses "
                    "sequence parallelism (global causal attention only)"
                )
            # ragged_decode composes: the kernel takes a window bound and
            # reads only [length - window, length) per row — exact for the
            # contract layout (slot == position), which is the same layout
            # the ragged contract already demands.
    # MoE (expert parallelism); num_experts == 0 -> dense MLP.
    num_experts: int = 0
    num_experts_per_token: int = 2
    # Per-expert buffer = capacity_factor * k * tokens / num_experts; tokens
    # routed past a full expert are dropped (standard GShard semantics).
    moe_capacity_factor: float = 1.25
    # Weight of the Switch-style load-balance aux loss added by lm_loss.
    moe_aux_loss_weight: float = 0.02
    # Routing rule (layers.route_experts).  "softmax": top-k of the router
    # logits, gates = softmax over the k chosen (Mixtral).  "sigmoid": scores
    # = sigmoid(logits) in float32; the chosen set is top-k of score + a
    # per-expert selection bias (``moe_expert_bias``: the bias picks, it
    # does not weigh), weights = the chosen scores, divided by their sum +
    # 1e-6 when ``moe_norm_topk``, times ``moe_routed_scale`` (LFM2-MoE).
    # (``moe_norm_topk`` and ``conv_kernel`` below mirror published keys and
    # have ONE value in use, LFM2's: constants until a second model needs
    # another.  ``moe_routed_scale`` has three: 1, 2.5, 5.)
    moe_score_fn: str = "softmax"
    # What an expert layer's router reads.  "ffn_norm": the FFN norm's
    # output, what the experts read (every model but one).  "block_input":
    # the block's input itself, before ``ln1`` and the operator, so the
    # routing of a layer is known before its attention runs
    # (SmallThinker: "router placed before attention"); the hybrid family's
    # alone (models.model.run_layers carries the logits to the experts).
    moe_router_input: str = "ffn_norm"
    moe_expert_bias: bool = False
    moe_norm_topk: bool = True
    moe_routed_scale: float = 1.0
    # Which expert layer a block runs.  True: layers.moe_swiglu, the GShard
    # capacity buffers above, which drop what overflows and give the
    # load-balance loss (mixtral-8x7b / moe-tiny as they are trained).
    # False: layers.moe_dropless, every token gets its k experts whatever
    # the other tokens chose (pairs grouped by expert, one grouped matmul:
    # ops/moe_experts.py), so a row's logits never depend on its
    # batch-mates: what a SERVED model needs, and the only rule the hybrid
    # family has.
    moe_capacity: bool = True
    # Expert FFN width where it differs from the dense one (None: the same).
    moe_intermediate_size: int | None = None
    # Leading layers whose FFN is dense although num_experts > 0.
    num_dense_layers: int = 0
    # Per-layer operator, for a model whose layers differ ("hybrid" family):
    # "conv" (gated short convolution, layers.short_conv), "attn" (GQA over
    # the whole prefix), "swa" (the same weights' shapes, over the last
    # ``sliding_window`` positions), "mla" or "ret" (power retention of
    # degree 2: GQA's projections and a scalar gate a key/value head, the
    # row's whole memory one float32 state a key/value head and no key:
    # ops/retention.py) or "ssm" (Mamba-2, the ssm_* fields below: a float32
    # state a head and the convolution's last inputs a row, beside the
    # "attn" layers' pages) or "gdn" (Gated DeltaNet, the gdn_* fields
    # below: a float32 state a value head, corrected by a rank-one
    # delta rule, and the convolution's last inputs, beside the pages too).
    # Empty for the families whose layers are all alike.
    layer_types: tuple[str, ...] = ()
    # Tokens a chunk of a "ret" layer's admission scan (ops/retention.py):
    # the attention form inside a chunk, the state between chunks.
    ret_chunk: int = 256
    # Whether a hybrid model's "attn" layers rotate queries and keys (its
    # "swa" layers always do).  False: no position enters a full layer
    # (EXAONE 4.0's hybrid attention).
    attn_rope: bool = True
    # Taps of the depthwise causal convolution of a "conv" layer; its whole
    # memory is the last ``conv_kernel - 1`` gated inputs a row.
    conv_kernel: int = 3
    # RMS-normalise q and k per head (learned [head_dim] scales) before RoPE.
    qk_norm: bool = False
    # Multi-head latent attention (layer kind "mla"; the published keys of
    # DeepSeek-V2's family, which A.X-K1 follows).  Queries go through a
    # rank-``q_lora_rank`` bottleneck; keys and values are up-projections of
    # ONE latent of ``kv_lora_rank`` a token, which is what is cached,
    # beside one rotated key of ``qk_rope_head_dim`` that all heads share.
    # A head's query and key are [nope | rope] wide, its value v_head_dim.
    # The rope part rotates the pairs (2i, 2i + 1) together (DeepSeek's
    # layout; the other families rotate (i, i + half)).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0  # > 0: the model's "mla" layers exist
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # "llama3" (the four rope_* fields above) or "yarn": frequencies whose
    # wavelength the original context holds fewer than ``yarn_beta_slow``
    # times are divided by the factor, those it holds more than
    # ``yarn_beta_fast`` times are kept, a linear ramp between; the
    # attention scale grows by (0.1 * yarn_mscale_all_dim * ln(factor) +
    # 1)^2 (models.model.mla_scale) and cos/sin by mscale(yarn_mscale) /
    # mscale(yarn_mscale_all_dim).
    rope_scaling_type: str = "llama3"
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # Experts every token goes through beside its routed ones (one SwiGLU
    # of n_shared_experts x expert_size).
    n_shared_experts: int = 0
    # Selection by groups (layers.route_experts): the experts are
    # ``moe_n_group`` consecutive runs, a group scores as its best expert,
    # and the top-k is taken among the ``moe_topk_group`` best groups.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # Added to the chosen scores' sum before the division (moe_norm_topk).
    moe_norm_eps: float = 1e-6
    # A chip's share of the routed experts: the stacks hold experts
    # [experts_offset, experts_offset + experts_held) of num_experts (None:
    # all).  The router keeps num_experts outputs; pairs routed to an
    # expert that is not held are left out of the layer's sum
    # (layers.moe_dropless), as they are on a chip of an expert-parallel
    # deployment before the combine.
    experts_held: int | None = None
    experts_offset: int = 0

    # Blocks of a hybrid model that are an operator ALONE, with no FFN
    # behind it (indices into ``layer_types``).  A published stack of single
    # sub-layers ``x <- x + f(rms(x))`` folds into (operator, FFN) blocks;
    # where two operators follow each other the first is such a block.
    no_ffn_layers: tuple[int, ...] = ()
    # Mamba-2 (layer kind "ssm", ops/ssm.py): ``ssm_heads`` heads of
    # ``ssm_head_dim`` (d_inner their product), B and C shared by the
    # heads of one of ``ssm_groups`` groups, a state [head_dim, ssm_state]
    # a head in float32, ``ssm_conv_kernel`` taps of a causal depthwise
    # convolution over [x | B | C], ``ssm_chunk`` tokens a chunk of an
    # admission's scan.
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # LatentMoE: the routed experts read and write a latent of this width
    # (``h W_dn``, the weighted sum back through ``W_up``); the router and
    # the shared expert read the hidden width.  0: experts at the hidden
    # width.  With it the experts are NOT gated (``gate_act`` "relu2":
    # ``relu(x W_up)^2 W_down``, two matrices an expert), and so is the
    # shared expert, ``moe_shared_intermediate_size`` wide.
    moe_latent_size: int = 0
    moe_shared_intermediate_size: int | None = None
    # Gated DeltaNet (layer kind "gdn", ops/gdn.py; Qwen3-Next's
    # ``linear_*`` keys): ``gdn_key_heads`` key (and query) heads of
    # ``gdn_key_dim``, ``gdn_value_heads`` value heads of ``gdn_value_dim``
    # (value head h reads key head h // (value heads / key heads)), a state
    # [key_dim, value_dim] a value head in float32, ``gdn_conv_kernel`` taps
    # of a causal depthwise convolution over [q | k | v], ``gdn_chunk``
    # tokens a chunk of an admission's scan.
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv_kernel: int = 4
    gdn_chunk: int = 64
    # The hybrid family's "attn" layers gate their output: ``W_q`` is twice
    # as wide, a head's outputs [query | gate], and the attention's output
    # is multiplied by ``sigmoid(gate)`` before ``W_o`` (Qwen3-Next).
    attn_out_gate: bool = False
    # The shared expert's output is multiplied by ``sigmoid(h w_s)``, one
    # scalar a token (``shared_expert_gate``, Qwen2-MoE's and Qwen3-Next's).
    moe_shared_gate: bool = False

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def expert_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def attn_layers(self) -> tuple[int, ...]:
        """Indices of the layers that hold keys and values: every layer,
        but for a hybrid model only its "attn" ones (the KV cache's and the
        page pool's layer axis counts these)."""
        if not self.layer_types:
            return tuple(range(self.num_layers))
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t in ("attn", "mla"))

    @property
    def swa_layers(self) -> tuple[int, ...]:
        """Indices of the layers that keep a ring of ``sliding_window``
        keys and values a row (kv_cache.HybridCache.ring_k): not paged."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "swa")

    @property
    def model_window(self) -> int | None:
        """``sliding_window`` where it is every layer's (the llama family:
        masks and kernel bands over ONE cache); None for a hybrid model,
        whose window is its "swa" layers' own."""
        return None if self.layer_types else self.sliding_window

    @property
    def held_experts(self) -> int:
        """Experts the stacks hold (all of them unless ``experts_held``)."""
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    @property
    def latent_width(self) -> int:
        """Lanes of a token's row in a latent page: the latent and the
        shared rotated key side by side, padded to whole 128-lane rows
        (the device tiles a row by 128 lanes, so 576 values occupy 640
        wherever they lie; the pad lanes hold zeros).  0 without MLA."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def conv_layers(self) -> tuple[int, ...]:
        """Indices of the layers that keep convolution state a row."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "conv")

    @property
    def ssm_layers(self) -> tuple[int, ...]:
        """Indices of the Mamba-2 layers, which keep a float32 state a row
        and a head and the convolution's last inputs
        (kv_cache.HybridCache.ssm_h / ssm_conv) BESIDE the page pool of
        the attention layers."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "ssm")

    @property
    def gdn_layers(self) -> tuple[int, ...]:
        """Indices of the gated delta-rule layers, which keep a float32
        state a row and a value head and the convolution's last inputs
        (kv_cache.HybridCache.gdn_s / gdn_conv) BESIDE the page pool of the
        attention layers."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "gdn")

    @property
    def scan_chunk(self) -> int:
        """Tokens a chunk of an admission's scan in a model whose rows hold a
        recurrent state (the "ret", "ssm" or "gdn" layers' own field); 0 for
        a model without one."""
        return (self.ret_chunk if self.ret_layers
                else self.ssm_chunk if self.ssm_layers
                else self.gdn_chunk if self.gdn_layers else 0)

    @property
    def gdn_key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def gdn_value_width(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def gdn_conv_width(self) -> int:
        """Channels the convolution runs over: [q | k | v]."""
        return 2 * self.gdn_key_width + self.gdn_value_width

    @property
    def ssm_inner(self) -> int:
        """Mamba-2's d_inner: heads x head size."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels the convolution runs over: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def shared_size(self) -> int:
        """Width of the shared expert."""
        return (self.moe_shared_intermediate_size
                or self.n_shared_experts * self.expert_size)

    @property
    def ffn_kinds(self) -> tuple[str | None, ...]:
        """The FFN of each block of a hybrid model: "dense", "moe", or
        None (``no_ffn_layers``)."""
        return tuple(
            None if l in self.no_ffn_layers
            else "dense" if l < self.num_dense_layers or not self.num_experts
            else "moe" for l in range(self.num_layers))

    @property
    def ret_layers(self) -> tuple[int, ...]:
        """Indices of the power-retention layers, which keep a float32
        state a row and a key/value head and no key
        (kv_cache.HybridCache.ret_s / ret_z)."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == "ret")


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh.  Axes follow the scaling-book convention:

    - ``data``:  batch sharding (data parallelism)
    - ``pipe``:  pipeline stages (the reference's layer-sharding, done right)
    - ``model``: tensor parallelism (attention heads / MLP hidden)
    - ``seq``:   sequence/context parallelism (ring attention)
    - ``expert``: expert parallelism for MoE layers
    """

    data: int = 1
    pipe: int = 1
    model: int = 1
    seq: int = 1
    expert: int = 1

    @property
    def axis_names(self) -> tuple[str, ...]:
        return ("data", "pipe", "model", "seq", "expert")

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.data, self.pipe, self.model, self.seq, self.expert)

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclass(frozen=True)
class RuntimeConfig:
    """Serving/runtime knobs (decode loop, KV cache, microbatching)."""

    max_seq_len: int = 1024
    max_decode_steps: int = 64
    microbatches: int = 1  # pipeline microbatches per step
    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0
    top_p: float = 1.0
    kv_cache_dtype: str = "bfloat16"
    # Session KV residency: with kv_host_spill, at most max_resident_sessions
    # session caches stay in HBM; least-recently-used ones spill to host DRAM
    # and are restored (async device_put) on their next turn.
    kv_host_spill: bool = False
    max_resident_sessions: int = 4
    # Weight-only quantized serving: keep an int8/int4 store's decoder-block
    # weights quantized in device memory; the blockwise dequant fuses into
    # each layer's matmuls (halves/quarters weight HBM + read bandwidth).
    serve_quantized: bool = False
    remat: bool = False  # jax.checkpoint on decoder blocks
    seed: int = 0
    profile_dir: str | None = None  # capture jax.profiler traces of generate
    # Paged KV cache for continuous batching (runtime/batcher.py): rows
    # allocate pages from a shared pool instead of owning max_seq_len slots;
    # a dry pool back-pressures admission.  None = contiguous per-slot KV.
    paged_pages: int | None = None
    page_size: int = 64
    # Automatic prefix caching over the paged pool (runtime/batcher.py
    # PrefixCache): full prompt pages are content-hashed and shared
    # copy-free across rows (refcounted; LRU eviction under pool
    # pressure), so repeated prompt prefixes — system prompts, few-shot
    # templates, multi-turn history — prefill only their un-cached
    # suffix.  Requires paged_pages; ignored (with a warning) otherwise.
    prefix_cache: bool = False
    # KV memory tiering (runtime/batcher.py, paged mode only):
    # kv_bits=8 stores pool pages as int8 with blockwise absmax scales —
    # roughly half the KV bytes per token, so ~1.9x concurrent rows per
    # pool byte; dequant fuses into the decode-attention read and greedy
    # outputs are parity-bounded (not bit-exact) vs bf16 pages.  16 = the
    # full-width kv_cache_dtype pool.
    kv_bits: int = 16
    # Host-RAM tier behind the paged pool, in pages: preemption SWAPS
    # victim rows out (byte-exact restore instead of prefix recompute;
    # exact-recompute fallback when the budget is dry) and cold
    # prefix-cache pages spill there before LRU eviction (a later hit
    # restores instead of re-prefilling).  0 disables.
    host_pages: int = 0
    # Dispatch-ahead engine loop (runtime/batcher.py): while no scheduling
    # work is pending, decode chunk N+1 dispatches directly from chunk N's
    # device-resident carry and chunk N's host work (token D2H, streaming
    # delivery, digest hashing, metrics) overlaps N+1's device execution.
    # Temp-0 outputs are byte-identical either way; admission/growth/
    # preemption semantics are unchanged (every scheduling decision still
    # runs against synced host mirrors).  Off = the fully-synchronous
    # loop, one host round-trip per chunk.
    overlap: bool = True
    # Scheduling policy (runtime/scheduler.py): "mixed" (default) fuses
    # pending prefill-chunk bites into the decode step as one compiled
    # token-budget program, so resident decode rows never stall for a
    # serialized prefill forward and the dispatch-ahead span keeps
    # running while a long prompt admits; "alternate" keeps the classic
    # serialized prefill_chunk_step rounds.  Temp-0 token streams are
    # byte-identical either way — this is a latency knob, not a
    # semantics knob.
    schedule: str = "mixed"
    # Per-step token budget the mixed policy sizes prefill bites
    # against: each fused step runs one decode leg per active slot plus
    # up to token_budget - n_active prompt tokens of the head pending
    # prefill.  Set, it also auto-chunks any prompt longer than the
    # budget even when prefill_chunk was never configured.  None/0 =
    # prefill_chunk-sized bites (fusion without re-budgeting).
    token_budget: int | None = None
    # Speculative decoding (runtime/speculative.py).  With spec_decode=True
    # on a single-device full-precision engine, generate_text transparently
    # routes greedy requests through the speculative loop (results are
    # bit-identical by construction — the draft only changes speed); the
    # draft is the engine's own blocks weight-only quantized to
    # spec_draft_quantize bits (self-speculation).  temperature > 0 and
    # mesh engines fall back to the plain decode loop.
    spec_decode: bool = False
    spec_k: int = 4
    spec_draft_quantize: int = 4
    # Adaptive spec_k downshift (greedy engines, schedule=mixed): per-row
    # acceptance-rate EMAs feed the scheduler's spec_round_k hook, which
    # clamps each row's COMMITTED tokens per round against the per-step
    # token budget.  The clamp is a ledger bound (a round never commits
    # more than the budget; cancel/deadline checks run at bounded
    # intervals) — the compiled round's device work is CONSTANT by design
    # (full k-draft + (k+1)-token verify, one compile key), so the clamp
    # trades commit granularity, never flops.  Streams stay byte-exact at
    # any clamp (the forced stop emits the target's own token); only
    # arrival granularity changes.
    spec_adaptive_k: bool = True
    # Deterministic fault injection (runtime/faults.py): a comma-separated
    # spec like "batcher.decode:raise@3,proto.send/HEARTBEAT:drop@1+".
    # Engine/batcher hot paths and the cluster protocol framing consult the
    # parsed FaultPlane; the serving supervisor's restart/re-admit path is
    # what this exists to exercise.  None disables.
    faults: str | None = None
    # Default per-request wall-clock deadline (seconds) applied by the
    # serving gateway when a request carries no "timeout_s" field of its
    # own.  An expired request cancels at the next chunk boundary and
    # returns finish_reason "timeout" with the tokens produced so far; one
    # that expires while still QUEUED is shed with 503 + Retry-After.
    # None = no default deadline.
    request_timeout_s: float | None = None
    # Estimated-cost admission gate (runtime/server.py): new requests 429
    # (with Retry-After) once queued + resident token mass exceeds this
    # multiple of the batcher's KV capacity — sustained overload sheds at
    # the front door instead of queueing work doomed to time out.
    # None/0 disables the gate.
    shed_cost_factor: float | None = 2.0
    # Grammar-constrained structured output (runtime/constrain.py): the
    # serving gateway's response_format={"type": "json_schema"|"regex"}
    # fields plus the logit_bias / banned_tokens ride-alongs.  False
    # answers every constrained request 400 (operator kill-switch —
    # automaton compiles are host CPU work an adversarial schema could
    # lean on).
    constrained_decoding: bool = True
    # LRU capacity of the compiled (constraint, tokenizer) -> token-mask
    # automaton cache: each entry holds two [n_states, vocab] tables, so
    # the capacity bounds host RAM spent on remembered schemas.
    constrain_cache_size: int = 64
    # Multi-tenant QoS (runtime/scheduler.py TenantScheduler + the
    # serving gateway's per-tenant quota gate).  tenant_weights turns on
    # weighted-fair admission: "gold:4,free:1"-style shares ("*" sets
    # the default weight unknown/anonymous tenants serve at), billed via
    # per-tenant virtual token counters — a tenant flooding the queue
    # advances its own counter and cannot crowd out a lighter tenant's
    # share.  None/"" = tenant-blind scheduling.
    tenant_weights: str | None = None
    # Per-tenant token-RATE quota at the serving gateway: admitted token
    # mass (prompt + budget) per second, PER UNIT WEIGHT — a tenant over
    # its rate sheds 429 with a per-tenant Retry-After before any
    # admission state exists.  None/0 disables rate quotas.
    tenant_quota_tps: float | None = None
    # Per-tenant RESIDENT-row cap in the batcher: a tenant at the cap
    # defers admission (others admit past it), so one tenant can never
    # hold every batch slot.  None/0 = uncapped.
    tenant_max_rows: int | None = None


@dataclass(frozen=True)
class ClusterConfig:
    """Control-plane knobs.  Replaces the reference's hard-coded ports and
    timers (src/master/node.py:15, src/worker/node.py:35,273)."""

    coordinator_host: str = "0.0.0.0"
    coordinator_port: int = 65432
    heartbeat_interval_s: float = 5.0
    heartbeat_timeout_s: float = 15.0  # deadline eviction (reference never evicts, D10)
    connect_retry_s: float = 5.0
    connect_max_retries: int = 5
    task_timeout_s: float = 60.0
    # Prometheus /metrics + /healthz + /status HTTP port on the coordinator
    # (implementation.md:34-37 parity). None disables; 0 binds ephemeral.
    metrics_port: int | None = None
    # jax.distributed settings for multi-host slices
    distributed_coordinator: str | None = None
    num_processes: int = 1
    process_id: int = 0


@dataclass(frozen=True)
class CheckpointConfig:
    """Shard-store / conversion knobs (successor of shard_info.json,
    src/model/shard_manager.py:63-74)."""

    cache_dir: str = "./models"
    shard_dir: str = "./shards"
    num_shards: int = 2
    quantization: str | None = None  # None | "int8" | "int4"
    quant_block_size: int = 128


@dataclass(frozen=True)
class Config:
    """Root config: everything the framework needs in one place."""

    model: ModelConfig = field(default_factory=ModelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    runtime: RuntimeConfig = field(default_factory=RuntimeConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    model_id: str = "gpt2"


def _dataclass_from_dict(cls: type, data: dict[str, Any]) -> Any:
    """Recursively build a (frozen) dataclass from a plain dict, rejecting
    unknown keys so config typos fail loudly."""
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(data) - set(fields)
    if unknown:
        raise ValueError(f"unknown config keys for {cls.__name__}: {sorted(unknown)}")
    kwargs: dict[str, Any] = {}
    for name, value in data.items():
        ftype = fields[name].type
        target = _nested_dataclass(ftype)
        if target is not None and isinstance(value, dict):
            kwargs[name] = _dataclass_from_dict(target, value)
        else:
            kwargs[name] = value
    return cls(**kwargs)


_NESTED = {
    "ModelConfig": ModelConfig,
    "MeshConfig": MeshConfig,
    "RuntimeConfig": RuntimeConfig,
    "ClusterConfig": ClusterConfig,
    "CheckpointConfig": CheckpointConfig,
}


def _nested_dataclass(ftype: Any) -> type | None:
    name = ftype if isinstance(ftype, str) else getattr(ftype, "__name__", "")
    return _NESTED.get(name)


def config_to_dict(cfg: Any) -> dict[str, Any]:
    return dataclasses.asdict(cfg)


def load_config(path: str | None = None, overrides: list[str] | None = None) -> Config:
    """Load a :class:`Config` from a JSON/YAML file plus dotted overrides.

    Overrides look like ``model.num_layers=24`` or ``mesh.pipe=4``; values are
    parsed as JSON when possible, else kept as strings.
    """
    data: dict[str, Any] = {}
    if path is not None:
        with open(path) as f:
            if path.endswith((".yaml", ".yml")):
                if not _HAVE_YAML:  # pragma: no cover
                    raise RuntimeError("yaml not available; use JSON config")
                data = yaml.safe_load(f) or {}
            else:
                data = json.load(f)
    for ov in overrides or []:
        key, _, raw = ov.partition("=")
        if not _:
            raise ValueError(f"override must be key=value, got {ov!r}")
        try:
            value = json.loads(raw)
        except json.JSONDecodeError:
            value = raw
        node = data
        parts = key.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return _dataclass_from_dict(Config, data)


def save_config(cfg: Config, path: str) -> None:
    with open(path, "w") as f:
        if path.endswith((".yaml", ".yml")) and _HAVE_YAML:
            yaml.safe_dump(config_to_dict(cfg), f)
        else:
            json.dump(config_to_dict(cfg), f, indent=2)
