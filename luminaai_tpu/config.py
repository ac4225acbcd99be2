"""Configuration system for the LuminaAI TPU-native framework.

Covers the reference's config surface (ref: Src/Main_Scripts/config/config_manager.py:15
``Config``, :759 ``ConfigPresets``, :1871 ``ConfigManager``) re-designed for TPU:
the DeepSpeed/NCCL fields are replaced by a `jax.sharding.Mesh` axis layout
(data / fsdp / tensor / expert / sequence parallelism).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

try:
    import yaml

    _HAS_YAML = True
except Exception:  # pragma: no cover
    _HAS_YAML = False

MOE_PATTERNS = ("all", "every_3rd", "every_4th", "sandwich", "none")
LR_SCHEDULES = ("cosine", "linear", "constant", "wsd")
PRECISIONS = ("auto", "fp32", "bf16", "mixed_bf16", "fp16", "mixed_fp16")


@dataclass
class Config:
    """Single source of truth for model + training + runtime configuration.

    Field groups mirror the reference Config (config_manager.py:15) with
    TPU-native parallelism fields replacing the DeepSpeed group.
    """

    # --- Model architecture ---
    vocab_size: int = 50304
    hidden_size: int = 512
    num_layers: int = 8
    num_heads: int = 8
    num_kv_heads: Optional[int] = 4
    seq_length: int = 1024
    intermediate_size: Optional[int] = None  # auto: 8/3 * hidden, rounded
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dropout: float = 0.0
    tie_word_embeddings: bool = True
    use_stable_embedding: bool = True
    init_std: float = 0.02
    use_flash_attention: bool = True
    flash_block_q: int = 1024
    flash_block_kv: int = 1024
    # RoPE rotation math: 'fp32' (exact tables; costs an fp32 [B,S,H,D]
    # round-trip per q/k projection, ~70ms/step at flagship scale) or
    # 'bf16' (rotation in the compute dtype; inputs/outputs are bf16-
    # quantized either way, only the products round differently).
    rope_dtype: str = "fp32"
    # Decode KV cache storage: 'bf16' (compute dtype) or 'int8' (per
    # position/head symmetric codes + fp32 scales — halves cache HBM, so
    # max batch·context doubles; the dequant convert fuses into the
    # attention dots). Quantization happens at insert; prefill/decode
    # math is otherwise unchanged.
    kv_cache_dtype: str = "bf16"
    # Serving attention backend for the length-aware (LaneMeta) decode/
    # prefill paths — scalar-offset decode, batched per-lane decode over
    # the slot-paged pool, and chunked prefill all dispatch through it
    # (ops/ragged_paged_attention.py):
    #   'dense'      legacy full-extent per-lane masking (parity oracle);
    #   'ragged_xla' length-aware attention, the serving default. Off a
    #                TPU the pure-XLA length-masked reference:
    #                bit-identical to 'dense' on resident rows, K/V sliced
    #                to the resident page extent so decode cost scales
    #                with tokens resident, not pool capacity;
    #   'ragged'     the same; off a TPU a decode batch's attention runs
    #                the Pallas kernel (lane_attention) INTERPRETED (slow:
    #                parity tests, not CPU serving).
    # On a TPU the two strings run one program: a decode batch takes
    # lane_attention where the static shapes are eligible
    # (lane_attention_eligible: the pool's layout alone: every array of a
    # layer's entry whole lanes wide, its row [kv_heads, width] whole
    # (8, 128) tiles, one head, or 4 heads of one 128-lane tile (a key
    # wider than its value lies in value-width parts: key_parts), at any
    # number of query heads a k/v head, MHA included) and the XLA
    # reference otherwise; nothing else chooses.
    # The single-stream engine's rolling cache (one uniform
    # attention_window, below) always takes the dense path: its slot
    # arithmetic is mod-C, which LaneMeta does not describe. The slot-paged
    # pool never rolls so: a layer with a window of its own
    # (layer_windows) keeps a RING OF PAGES a lane there, addressed through
    # a page table at absolute positions, and every backend reads it.
    attention_backend: str = "ragged_xla"
    # Chunked prefill: prompts prefill in fixed chunks of this many
    # tokens — ONE executable for every prompt length (instead of a
    # power-of-two bucket ladder), and the serving scheduler interleaves
    # chunks with decode steps so a long admission cannot stall the
    # decode batch for more than ~one chunk's step time. 0 disables
    # (legacy bucketed prefill). The single-stream engine ignores it under
    # one uniform attention_window (its rolling cache takes whole prompts);
    # a pool with rings of pages (layer_windows) needs it: a ring is sized
    # to the window plus one chunk.
    prefill_chunk_size: int = 64
    # Radix prefix cache over the serving KV pool (inference/
    # prefix_cache.py): budget of content-hash-keyed arena pages shared
    # copy-on-write across lanes — admissions splice the longest cached
    # prompt-prefix page chain into their page table and prefill only
    # the uncached suffix. 0 disables. Requires a ragged attention
    # backend (the dense mask cannot follow cross-slot aliases; the
    # decoder gates the cache off under 'dense') and chunked prefill.
    prefix_cache_pages: int = 0
    # Max arena pages one tenant may own (0 = unbounded): a hot tenant
    # at quota evicts its OWN pages, never everyone else's.
    prefix_cache_tenant_quota: int = 0
    # Sliding-window (local) attention: each position attends to at most
    # the `attention_window` most recent positions (itself included).
    # None = full causal. The flash kernels skip whole blocks outside the
    # band, so long-context attention cost becomes O(S·W) instead of
    # O(S²); ring sequence parallelism masks/skips the same band across
    # shards; the single-stream engine (generate()) decodes from a ROLLING
    # KV cache (slot = pos % C, C ≈ W), so its cache HBM is O(window)
    # instead of O(max_context); the slot-paged pool keeps whole pages
    # under this one uniform window and masks the band. A TPU-first
    # capability beyond the reference's surface (its attention is always
    # full causal).
    attention_window: Optional[int] = None
    # A window a LAYER, data beside layer_mixers: num_layers entries, each
    # a window or None (full causal). None = every attention layer takes
    # attention_window. A layer with a window of its own is masked by it
    # everywhere, and in the slot-paged pool keeps a ring of
    # ceil((window + prefill_chunk_size) / page_size) + 1 pages a lane
    # where a full layer keeps all of them (GQAttention.init_cache,
    # docs/serving.md).
    layer_windows: Optional[tuple] = None
    # Token mixer of each layer, a tuple of num_layers entries:
    #   'attention' GQAttention (RoPE, the KV cache, every serving path);
    #   'latent'    LatentAttention (models/layers.py): keys and values
    #               expanded from one low-rank latent a token, scores
    #               over nope+rope dims and values of v_head_dim; the
    #               rope parts rotate under latent_rope (YaRN's
    #               frequencies under yarn_factor), queries are low-rank
    #               under q_lora_rank; trained in the expanded form,
    #               served in the absorbed form over a paged entry of
    #               ONE latent row a token (every serving path but the
    #               prefix cache);
    #   'kda'       KimiDeltaAttention (models/kda.py): the gated delta
    #               rule as a linear-attention recurrence over a
    #               [head_dim x head_dim] state a head, computed by the
    #               chunked Pallas kernels of ops/kda.py (training only);
    #   'ssm'       SelectiveSSM (models/ssm.py): Mamba-1's selective scan
    #               with Jamba's inner norms over a [state x channels]
    #               float32 state; trained by a chunked XLA scan, served
    #               by ops/ssm.py's kernel with a fixed state a lane
    #               beside the pages of k/v (every serving path but the
    #               prefix cache, page pulls and speculation);
    #   'ssm2'      ScalarDecaySSM (models/ssm.py): Mamba-2's heads, ONE
    #               decay and ONE step a head (ssm2_num_heads of
    #               ssm2_head_dim), B and C shared by ssm2_groups groups
    #               of heads, the convolution over [x, B, C], a gated
    #               RMSNorm by group behind the scan; the same
    #               [state x channels] float32 LaneState as 'ssm'.
    #               Trained and prefilled in the block (matmul) form of
    #               ops/ssm.py::block_scan, the lanes stepped by
    #               ops/ssm.py::ssm_scan_heads;
    #   'none'      no mixer: the layer is its feed-forward alone behind
    #               ONE norm, and a lane keeps nothing of it (no cache
    #               entry: init_cache's None).
    # None = every layer 'attention'. scan_layers needs one kind.
    layer_mixers: Optional[tuple] = None
    # Feed-forward of each layer, beside layer_mixers: 'dense' (SwiGLU),
    # 'moe' (MoELayer) or 'none' (the layer is its mixer alone behind ONE
    # norm: x + mixer(norm(x))). None = by moe_pattern (is_moe_layer),
    # every layer with a feed-forward. Set, it IS the placement:
    # is_moe_layer reads it and moe_pattern is not consulted.
    layer_ffns: Optional[tuple] = None
    # False: GQAttention rotates nothing (a stack whose recurrent layers
    # carry the position, as Jamba's).
    use_rope: bool = True
    # A rotation a LAYER (num_layers booleans; None = use_rope for all):
    # window layers that rotate beside full layers that do not.
    layer_rope: Optional[tuple] = None
    # Which pairs a rotation turns: 'split' (x[i], x[i + d/2]: rotate_half)
    # or 'interleaved' (x[2i], x[2i+1]: GPT-J's). The same model under a
    # permutation of each head's q and k columns.
    rope_layout: str = "split"
    # Size of one attention head; None = hidden_size // num_heads.
    attn_head_dim: Optional[int] = None
    # GQAttention's k/v heads a LAYER (num_layers entries; None =
    # num_kv_heads for all): window layers of 8 beside full layers of 4.
    layer_kv_heads: Optional[tuple] = None
    # Width of a GQAttention value head (and of the head's output); None
    # = the key's (attn_head_dim). A key wider than its value is KEPT in
    # value-width parts, the last zero-padded (Config.key_parts: 192 over
    # 128 is two arrays of 128 columns a layer beside the value's one), so
    # that every array of the entry is [rows, kv_heads, value width] and
    # the pool's rows lie as the kernels read them.
    attn_value_dim: Optional[int] = None
    # Values are multiplied by this before attention (1.0: not at all).
    attn_value_scale: float = 1.0
    # Columns of a q / k head that a rotation turns, the FIRST rope_dim
    # (pairs by rope_layout within them); the rest carry no position.
    # None = the whole head.
    rope_dim: Optional[int] = None
    # Rotation base a LAYER (num_layers entries; None = rope_theta).
    layer_rope_theta: Optional[tuple] = None
    # A learned SINK a layer (num_layers booleans; None = nowhere): one
    # float32 logit a query head (parameter `sink` [num_heads], there
    # alone) that joins the softmax's denominator and gives no value:
    # p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(sink_h)). Drawn
    # N(0, attn_sink_init_std) (0: a sink of logit 0, not none).
    layer_sink: Optional[tuple] = None
    attn_sink_init_std: float = 0.0
    # 'rms' (RMSNorm, rms_norm_eps) or 'layernorm' (mean-subtracting, no
    # bias, layer_norm_eps): the blocks' norms and the final norm.
    norm_kind: str = "rms"
    layer_norm_eps: float = 1e-5
    # True: ONE norm a layer feeds the mixer AND the feed-forward, and both
    # are added to the residual (x + attn(n(x)) + ffn(n(x))); no ffn_norm.
    parallel_block: bool = False
    ssm_state_size: int = 16
    ssm_dt_rank: Optional[int] = None  # None = ceil(hidden_size / 16)
    ssm_expand: int = 2
    ssm_conv_size: int = 4
    # An 'ssm2' layer: heads x head width is its inner width (the state is
    # [ssm_state_size, heads x head width] float32 a lane), B and C are
    # shared by ssm2_groups groups of heads (the convolution runs over
    # inner + 2 x groups x ssm_state_size channels), ssm2_chunk rows a
    # block of the matmul form. The step's bias is drawn as the inverse
    # softplus of a log-uniform step in [ssm2_dt_min, ssm2_dt_max]
    # floored at ssm2_dt_floor, A_log as log U(1, 16) a head.
    ssm2_num_heads: int = 8
    ssm2_head_dim: int = 64
    ssm2_groups: int = 1
    ssm2_chunk: int = 128
    ssm2_dt_min: float = 1e-3
    ssm2_dt_max: float = 1e-1
    ssm2_dt_floor: float = 1e-4
    kda_num_heads: Optional[int] = None  # None = num_heads
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Low-rank queries of a 'latent' layer: q = W_qb RMSNorm(W_qa x).
    # None: one plain projection.
    q_lora_rank: Optional[int] = None
    # True: a 'latent' layer rotates the qk_rope_head_dim parts of q and
    # of the shared key (rope_theta, rope_layout). False: nothing rotates
    # (a stack whose recurrent layers carry the position).
    latent_rope: bool = False
    # YaRN on a 'latent' layer's rotation (needs latent_rope): the
    # frequencies that turn fewer than yarn_beta_slow times over
    # yarn_original_max positions are divided by yarn_factor, those that
    # turn more than yarn_beta_fast times are kept, a linear ramp
    # between; cos and sin are multiplied by mscale(yarn_mscale) /
    # mscale(yarn_mscale_all_dim) and the softmax scale by
    # mscale(yarn_mscale_all_dim)^2, mscale(m) = 0.1 m ln(factor) + 1.
    # None: plain frequencies.
    yarn_factor: Optional[float] = None
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    # --- MoE ---
    use_moe: bool = False
    num_experts: int = 8
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    load_balancing_weight: float = 0.01
    router_z_loss_weight: float = 1e-3
    routing_temperature: float = 1.0
    routing_noise_std: float = 0.1
    # Whole-expert dropout during training: each step a Bernoulli mask
    # removes experts from routing, forcing load to spread (anti-collapse;
    # ref trainer.py:1495 enable_expert_dropout). 0 disables.
    expert_dropout_rate: float = 0.0
    moe_pattern: str = "all"
    dense_start_layers: int = 2
    dense_end_layers: int = 2
    expert_output_scaling: float = 1.0
    # The combine rule, as data read by the one routing helper
    # (models/moe.py `_sort_routing`): scores are softmax or sigmoid of
    # the router's logits; the k experts are the top-k of score (+ a
    # learned-elsewhere `selection_bias` parameter, used for the choice
    # alone); their scores are divided by their sum (renormalize) and
    # multiplied by the scale. The defaults are what this layer always
    # computed, bit for bit.
    moe_score_func: str = "softmax"  # softmax|sigmoid
    moe_selection_bias: bool = False
    # The selection bias is drawn N(0, this) (0: zeros, as a router that
    # nobody has balanced yet has it).
    moe_selection_bias_init_std: float = 0.0
    moe_renormalize: bool = True
    moe_routed_scale: float = 1.0
    # Width of one routed (and one shared) expert; None = intermediate_size.
    moe_intermediate_size: Optional[int] = None
    # Shared experts: one SwiGLU of num_shared_experts x the expert width
    # that every token passes through, added to the routed result: the SUM
    # of num_shared_experts SwiGLUs of the expert width ('sum') or their
    # mean ('average').
    num_shared_experts: int = 0
    shared_expert_combine: str = "sum"
    # The three below run under experts_held alone (the other dispatch
    # paths refuse them by name).
    # 'swiglu': W_down (silu(W_gate x) * W_up x), wi holds gate | up;
    # 'relu2': the non-gated W_down relu(W_up x)^2, wi holds W_up alone.
    moe_expert_act: str = "swiglu"
    # Experts in a latent: the layer projects its rows H -> moe_latent_size
    # (`fc1`) in front of dispatch and back (`fc2`) behind the combine, the
    # experts are moe_latent_size wide in and out; the router and the
    # shared expert read the un-projected row. None: experts at H.
    moe_latent_size: Optional[int] = None
    # A shared expert of its OWN width (one MLP of this width and of the
    # experts' activation on the un-projected row, added once): set, it
    # replaces num_shared_experts x the expert width.
    moe_shared_size: Optional[int] = None
    # (offset, count): this program holds experts [offset, offset+count)
    # of num_experts, as one chip of an expert-parallel group does. The
    # router keeps its num_experts outputs and its top-k; the layer
    # computes the held experts' part of the result (plus the shared
    # expert) and nothing stands in for the rest. gmm dispatch, no expert
    # mesh axis; trained (Trainer) and served (the tick program counts
    # routed / held / dropped pairs over its live rows): docs/parallelism.md.
    experts_held: Optional[tuple] = None
    # 'sort' = scatter/gather dispatch via flat slot ids (linear memory);
    # 'gather' = same routing, but the expert buffers are filled by a row
    # GATHER through an inverted slot→token index table (the H-wide scatter
    # moves to the backward pass — TPUs execute row gathers much better);
    # 'einsum' = GShard one-hot dispatch (O(S·E·C) memory, MXU-only data
    # movement — an A/B baseline);
    # 'a2a' = cross-host expert parallelism: tokens shard over
    # (data, fsdp, expert) and are ROUTED to their experts' shards via
    # the hierarchical (ici-then-dcn) all-to-all subsystem
    # (parallel/expert_dispatch.py) — padding-free bucket payloads, no
    # full-activation psum; requires an 'expert' mesh axis.
    moe_dispatch: str = "sort"
    # a2a only: how much of the expert axis spans the DCN tier (hosts).
    # expert_parallel_size must be divisible; 1 = single-stage fallback
    # (everything on ICI). The two-stage exchange sends few large
    # rail-aligned DCN messages per X-MoE (docs/parallelism.md).
    expert_dcn_size: int = 1
    # a2a only: split the bucket payload into this many chunks so each
    # chunk's stage-2 (DCN) exchange is data-independent of the other
    # chunks' expert FFN — XLA's latency-hiding scheduler overlaps
    # comms with grouped-matmul compute. 1 disables.
    moe_a2a_overlap_chunks: int = 2
    # Internal: explicit expert-axis activation constraints in MoELayer.
    # The pipeline builders flip this off inside the manual-pipe region
    # (XLA partitioner group-check crash); everywhere else leave True.
    moe_ep_constraints: bool = True
    # Internal: manual expert parallelism — tokens sharded over the
    # 'expert' mesh axis, explicit tiled all-to-alls around the expert
    # FFN. Set by the 1F1B pipeline builders (auto-SPMD ep cannot
    # partition inside the manual-pipe region); requires being inside a
    # shard_map with a manual 'expert' axis.
    moe_manual_ep: bool = False
    # Internal: call the ring-attention body directly (no nested
    # shard_map) — set by the 1F1B pipeline builders when sp > 1; requires
    # a manual 'sequence' axis in scope.
    ring_manual: bool = False
    # Internal: manual axes tokens are sharded over inside the pipeline
    # region; MoE routing stats pmean over these so aux/z losses use
    # global fractions.
    moe_stat_pmean_axes: tuple = ()

    # --- MoD (mixture of depths) ---
    use_mod: bool = False
    mod_capacity_factor: float = 0.5
    mod_routing_temperature: float = 1.0

    # --- Training ---
    batch_size: int = 8  # global batch (sequences)
    micro_batch_size: Optional[int] = None  # per grad-accum slice; auto
    gradient_accumulation_steps: int = 1
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip_norm: float = 1.0
    num_epochs: int = 3
    max_steps: Optional[int] = None
    warmup_ratio: float = 0.15
    lr_scheduler: str = "cosine"
    use_lr_scheduler: bool = True
    min_lr: float = 1e-6
    precision: str = "auto"  # auto|fp32|bf16|mixed_bf16|mixed_fp16
    inference_precision: str = "auto"
    # Weight-only inference quantization (training/quantization.py):
    # None | 'int8' | 'int4' (ref trainer.py:575 QuantizationManager).
    quantization_method: Optional[str] = None
    quantization_bits: int = 8
    gradient_checkpointing: bool = True
    # nothing_saveable = recompute everything (min HBM);
    # save_outs = store each block's attention/FFN outputs (2 x [B,S,H]
    #   bf16 per layer) so the backward recomputes only the branch being
    #   differentiated — most of dots_saveable's win at ~1% of its HBM;
    # dots_saveable = store every matmul output; full = no remat.
    remat_policy: str = "nothing_saveable"  # nothing_saveable|save_outs|save_attn|dots_saveable|full
    # Adam first-moment dtype: None = fp32; 'bf16' halves mu's HBM
    # (2 bytes/param) — nu stays fp32 (variance needs the exponent range).
    adam_mu_dtype: Optional[str] = None
    # 'int8': both Adam moments as int8 codes + row-wise scales (1B/param/
    # moment vs 4; ref trainer.py:771 create_quantized_optimizer).
    adam_state_quantization: Optional[str] = None
    scan_layers: bool = False  # lax.scan over layers (homogeneous stacks)
    donate_state: bool = True
    eval_every_n_batches: int = 500
    save_every_n_batches: int = 1000
    assistant_loss_weight: float = 1.5
    z_loss_weight: float = 0.0
    label_smoothing: float = 0.0
    # Fuse the LM head matmul into the CE loss, chunked over the sequence —
    # full [B,S,V] logits never materialize (ops/fused.py). The single
    # biggest HBM saving at large vocab; disable only for debugging.
    fused_lm_head_ce: bool = True
    loss_chunk_size: int = 256

    # --- Parallelism (replaces ref DeepSpeed/FSDP/ColossalAI group) ---
    # Axis order = physical torus placement: trailing axes land on the
    # innermost ICI ring, so the chattiest collectives (tensor) go last.
    mesh_axes: tuple = ("data", "pipe", "fsdp", "expert", "sequence", "tensor")
    data_parallel_size: int = -1  # -1 = infer remaining devices
    # GPipe pipeline parallelism over the scanned layer stack
    # (parallel/pipeline.py): stage p holds layers [p*L/P, (p+1)*L/P).
    pipeline_parallel_size: int = 1
    pipeline_microbatches: Optional[int] = None  # auto: = pipe size
    # '1f1b': fused fwd+bwd schedule, per-stage live activations bounded by
    # ~2P regardless of microbatch count (the PipeDream-flush memory
    # profile); 'gpipe': all-forward-then-autodiff (simpler, more live
    # activations — A/B and eval path).
    pipeline_schedule: str = "1f1b"
    fsdp_parallel_size: int = 1
    expert_parallel_size: int = 1
    tensor_parallel_size: int = 1
    sequence_parallel_size: int = 1
    use_ring_attention: bool = False  # required when sequence_parallel_size > 1
    allow_split_physical_axes: bool = False
    multihost: bool = False  # call jax.distributed.initialize()
    coordinator_address: Optional[str] = None
    process_id: Optional[int] = None
    num_processes: Optional[int] = None

    # --- Data ---
    train_data_path: str = "data/train.jsonl"
    eval_data_path: str = "data/eval.jsonl"
    tokenizer_name: str = "byte"  # byte|bpe:PATH|tiktoken:NAME|hf:NAME
    num_workers: int = 2
    max_conversations_per_file: int = 10000
    streaming_threshold_gb: float = 10.0
    prefetch_batches: int = 2
    pack_sequences: bool = True
    use_native_dataloader: bool = True  # C++ memmap packer when available

    # --- Generation ---
    max_new_tokens: int = 512
    temperature: float = 0.8
    top_p: float = 0.9
    top_k: int = 50
    repetition_penalty: float = 1.05

    # --- Production / experiment ---
    experiment_name: Optional[str] = None
    output_dir: str = "experiments"
    # Capture a jax.profiler device trace (TensorBoard XPlane) for steps
    # [profile_start_step, profile_start_step + profile_num_steps) into
    # profile_dir (default output_dir/profile). 0 disables (SURVEY §5
    # tracing). After the window closes the trainer runs the attribution
    # classifier (monitoring/attribution.py) over the trace and exports
    # the per-subsystem breakdown as registry gauges + attribution.jsonl.
    profile_start_step: int = 0
    profile_num_steps: int = 3
    profile_dir: Optional[str] = None
    # AOT-query XLA's cost model for the compiled train step at first
    # compile (compiled_flops_per_step / bytes_accessed / HBM-footprint
    # gauges + the analytic-vs-compiled MFU cross-check). Off by default:
    # the AOT lower+compile is a second compile of the step program
    # (cheap only where the persistent compile cache is warm).
    compiled_cost_analysis: bool = False
    seed: int = 42
    log_level: str = "INFO"
    save_total_limit: int = 5
    early_stopping_patience: Optional[int] = None
    auto_resume: bool = True
    backup_every_n_hours: int = 6
    max_retries: int = 3
    enable_wandb: bool = False
    wandb_project: Optional[str] = None
    wandb_entity: Optional[str] = None

    # --- Monitoring / fault tolerance ---
    health_check_interval: int = 100
    loss_spike_threshold: float = 2.0
    grad_norm_threshold: float = 100.0
    expert_collapse_threshold: float = 0.05
    # Goodput ledger + hang watchdog + step-time anomaly sentinel
    # (docs/observability.md "Goodput & sentinels"). The ledger
    # attributes every second of the run to a cause and exports
    # training_goodput_fraction; the watchdog heartbeats at the
    # log-window sync and fires when a beat gap exceeds
    # watchdog_k x (rolling median + MAD), floored at watchdog_floor_s
    # — warmup-aware, so the first compile can never trip it. All
    # host-side wall clock: zero new syncs on the step path.
    goodput: bool = True
    watchdog: bool = True
    watchdog_k: float = 10.0
    watchdog_floor_s: float = 30.0
    watchdog_warmup: int = 3
    watchdog_poll_s: float = 1.0
    # Opt-in (--watchdog-abort): a confirmed stall exits 75 (resumable)
    # after dumping stacks + the flight ring, so orchestrators restart
    # the job instead of burning the reservation on a wedged sync.
    watchdog_abort: bool = False
    # Step-time anomaly sentinel: a logged window mean flagged when it
    # exceeds step_anomaly_k x rolling median (+ MAD significance
    # guard). step_anomaly=False silences a known-noisy workload
    # (no gauges, no events).
    step_anomaly: bool = True
    step_anomaly_k: float = 4.0
    # --- SLO engine (docs/observability.md "SLOs & burn rate") ---
    # A background sampler retains windowed history of the registry in a
    # fixed-memory ring (counters as deltas, histograms as windowed
    # quantiles) and the SLO engine judges declarative objectives over
    # fast/slow windows with Google-SRE burn-rate rules: a fast-window
    # burn >= slo_fast_burn pages, a slow-window burn >= slo_slow_burn
    # warns, transitions land in the flight recorder as slo_burn events.
    # slo_config points at a JSON file REPLACING the default objectives.
    # All host-side: zero new syncs on the step path.
    slo: bool = True
    slo_sample_interval_s: float = 5.0
    slo_ring_points: int = 720       # per series (~1h at the default 5s)
    slo_max_series: int = 256        # hard series budget (then _overflow)
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 600.0
    slo_fast_burn: float = 10.0
    slo_slow_burn: float = 2.0
    slo_budget: float = 0.1          # allowed violating-sample fraction
    # Default objective targets (objectives_for builds them from these):
    slo_ttft_p95_s: float = 2.0      # serve: p95 time-to-first-token
    slo_decode_p50_s: float = 0.5    # serve: median per-token latency
    slo_error_rate: float = 0.05     # serve: shed+timeout / admissions
    slo_goodput_fraction: float = 0.5  # train: productive/elapsed floor
    slo_step_time_factor: float = 2.0  # train: p95 vs rolling median
    slo_config: Optional[str] = None   # JSON override (--slo-config)
    # --- Durable I/O (docs/resilience.md "Durable I/O") ---
    # Storage ops (checkpoint save/restore, manifest writes, data opens/
    # reads) retry transient faults with exponential backoff + jitter:
    # io_retries total attempts per op, delays io_retry_base_s doubling
    # up to io_retry_max_s, the whole op bounded by io_timeout_s when
    # set. Retry waits accrue to the already-open goodput cause
    # (checkpoint / data_wait).
    io_retries: int = 4
    io_retry_base_s: float = 0.05
    io_retry_max_s: float = 2.0
    io_timeout_s: Optional[float] = None
    # Checkpoint integrity: restore verifies each step's sha256 manifest
    # — 'full' hashes every file, 'sample' hashes a deterministic subset
    # (sizes always checked; the fast mode for huge checkpoints), 'off'
    # disables. A mismatch walks back like any corrupt checkpoint.
    checkpoint_verify: str = "full"
    # Emergency saves fall back to this local directory when the primary
    # checkpoint dir is unwritable (None disables the tier).
    checkpoint_local_tier: Optional[str] = None
    # Degraded-mode data loading: corrupt/truncated records are
    # quarantined (counted + flight-evented, run continues) instead of
    # raising; a quarantine rate above the fence aborts so silent data
    # loss can't masquerade as health.
    data_quarantine: bool = True
    data_quarantine_max_rate: float = 0.05
    # --- Serving-plane router (docs/serving.md "Replica router") ---
    # The data-plane router fronting N ChatServer replicas
    # (serving/router.py): health probes every router_probe_interval_s;
    # a replica's circuit breaker opens after router_breaker_failures
    # consecutive failures (or the error-rate threshold) and re-probes
    # half-open after router_breaker_cooldown_s; a failed dispatch
    # retries on up to router_max_failovers other candidates with
    # backoff+jitter. Hedged dispatch (opt-in, `lumina route --hedge`)
    # fires a second replica for short (< router_hedge_max_tokens)
    # non-stream requests after a p95-based delay, capped at
    # router_hedge_budget of non-stream traffic.
    router_probe_interval_s: float = 2.0
    router_breaker_failures: int = 3
    router_breaker_cooldown_s: float = 5.0
    router_max_failovers: int = 2
    router_hedge_budget: float = 0.1
    router_hedge_max_tokens: int = 32
    # Cross-replica KV page sharing (docs/serving.md "Cross-replica
    # prefix sharing"): replicas report harvested prefix-chain keys to
    # the router's page index and pull indexed pages directly from the
    # owning sibling on a cold admission. page_share enables the plane
    # (the serve CLI takes the router URL); page_pull_timeout_s bounds
    # one whole pull (lookup + transfers) before degrading to local
    # prefill; page_share_max_inflight caps concurrent pulls per
    # replica so transfers can't starve the decode loop.
    page_share: bool = False
    page_pull_timeout_s: float = 2.0
    page_share_max_inflight: int = 2

    # --- Adaptive control (orchestrator) ---
    enable_adaptive_lr: bool = True
    allow_scheduler_override: bool = True
    min_override_threshold: float = 0.2
    emergency_override_enabled: bool = True
    log_lr_decisions: bool = True
    enable_architecture_evolution: bool = False
    # Runtime capacity-factor / routing-temperature tuning (each change
    # recompiles the step; ref trainer.py:1450,1471).
    enable_moe_routing_optimization: bool = True
    # Orchestrator may raise AdamW weight decay on a slow sustained loss
    # rise (ref trainer.py:1792 adjust_weight_decay's adaptive role).
    enable_adaptive_wd: bool = True
    # Gradient-noise-driven effective-batch growth (recompiles + reshapes
    # the data contract; opt-in; ref trainer.py:1626).
    enable_batch_size_optimization: bool = False
    # Phase-scheduled MoD compute ratio (ref Main.py mod_capacity_adaptation
    # + trainer.py:1559 adjust_mod_capacity): spend more FFN compute early
    # in training, taper as the model converges. Total steps split into
    # len(schedule) equal phases; each change recompiles the step.
    enable_mod_capacity_adaptation: bool = False
    mod_capacity_schedule: tuple = (0.7, 0.5, 0.3)
    # Learning-velocity curriculum (ref chinchilla_scaler.py:155
    # AdaptiveCurriculumManager): the orchestrator tracks per-step loss
    # reduction and forwards the recommended difficulty to any data loader
    # exposing set_difficulty (PackedDataset maps it to a doc-length
    # quantile; takes effect at the next epoch restart).
    enable_adaptive_curriculum: bool = False
    intervention_cooldown_steps: int = 200

    # --- Chinchilla scaling ---
    use_chinchilla_scaling: bool = False
    tokens_per_param: float = 20.0
    convergence_patience: int = 5

    # --- Memory ---
    max_memory_usage: float = 0.9
    host_offload_optimizer: bool = False  # ref cpu_offload_* analogue

    def __post_init__(self):
        # yaml/json roundtrips turn tuples into lists; normalize back so
        # to_dict() comparisons and static hashing stay stable.
        self.moe_stat_pmean_axes = tuple(self.moe_stat_pmean_axes)
        if self.layer_mixers is not None:
            self.layer_mixers = tuple(self.layer_mixers)
        if self.layer_ffns is not None:
            self.layer_ffns = tuple(self.layer_ffns)
        if self.experts_held is not None:
            self.experts_held = tuple(int(x) for x in self.experts_held)
        if self.layer_windows is not None:
            self.layer_windows = tuple(
                None if not w else int(w) for w in self.layer_windows
            )
        if self.layer_rope is not None:
            self.layer_rope = tuple(bool(r) for r in self.layer_rope)
        if self.layer_kv_heads is not None:
            self.layer_kv_heads = tuple(int(n) for n in self.layer_kv_heads)
        if self.layer_rope_theta is not None:
            self.layer_rope_theta = tuple(
                float(t) for t in self.layer_rope_theta)
        if self.layer_sink is not None:
            self.layer_sink = tuple(bool(b) for b in self.layer_sink)
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size is None:
            # SwiGLU sizing: 8/3 * hidden, rounded up to a multiple of 128
            # (MXU lane width) — ref auto-calcs 4*hidden for plain FFN.
            raw = int(8 * self.hidden_size / 3)
            self.intermediate_size = ((raw + 127) // 128) * 128
        if self.micro_batch_size is None:
            self.micro_batch_size = max(
                1, self.batch_size // max(1, self.gradient_accumulation_steps)
            )
        elif (
            self.gradient_accumulation_steps == 1
            and 0 < self.micro_batch_size < self.batch_size
        ):
            # Explicit micro_batch_size drives the in-jit accumulation
            # split (the reference's dataloader-batch knob, ref
            # config_manager.py micro_batch_size).
            assert self.batch_size % self.micro_batch_size == 0, (
                "batch_size must be a multiple of micro_batch_size"
            )
            self.gradient_accumulation_steps = (
                self.batch_size // self.micro_batch_size
            )
        if isinstance(self.mesh_axes, list):
            self.mesh_axes = tuple(self.mesh_axes)
        if isinstance(self.mod_capacity_schedule, list):
            # yaml/json round-trips tuples as lists
            self.mod_capacity_schedule = tuple(self.mod_capacity_schedule)
        self.normalize_parallelism()
        self.validate()

    def normalize_parallelism(self) -> None:
        """Resolve axis-implied settings so a bare axis-size request is a
        complete, valid config. Runs in __post_init__ before validate(), so
        constructor/preset/file-loaded configs all get it (docs/
        parallelism.md):

          - sequence parallelism rides ring attention;
          - pipeline parallelism slices the scanned layer stack, and grad
            accumulation folds into pipeline microbatches (same memory
            effect, no extra bubbles), capped to a divisor of the batch.
            micro_batch_size is cleared so __post_init__ cannot re-derive
            the accumulation this fold just removed.
        """
        if self.sequence_parallel_size > 1 and not self.use_ring_attention:
            self.use_ring_attention = True
        if self.pipeline_parallel_size > 1:
            if not self.scan_layers:
                self.scan_layers = True
            if self.gradient_accumulation_steps > 1:
                n_micro = (
                    self.pipeline_microbatches or self.pipeline_parallel_size
                )
                cand = min(
                    n_micro * self.gradient_accumulation_steps,
                    self.batch_size,
                )
                # Loop exits with cand dividing batch_size, or cand ==
                # n_micro (whose divisibility validate() then checks).
                while cand > n_micro and self.batch_size % cand != 0:
                    cand -= 1
                self.pipeline_microbatches = cand
                self.gradient_accumulation_steps = 1
                self.micro_batch_size = self.batch_size

    # -- validation ------------------------------------------------------
    def validate(self) -> None:
        assert self.attn_head_dim or self.hidden_size % self.num_heads == 0, (
            "hidden_size must be divisible by num_heads"
        )
        assert self.attn_head_dim is None or self.attn_head_dim > 0
        assert self.rope_layout in ("split", "interleaved"), (
            f"invalid rope_layout {self.rope_layout}"
        )
        assert self.norm_kind in ("rms", "layernorm"), (
            f"invalid norm_kind {self.norm_kind}"
        )
        assert self.shared_expert_combine in ("sum", "average"), (
            f"invalid shared_expert_combine {self.shared_expert_combine}"
        )
        for name in ("layer_windows", "layer_rope", "layer_kv_heads",
                     "layer_rope_theta", "layer_sink"):
            per_layer = getattr(self, name)
            assert per_layer is None or len(per_layer) == self.num_layers, (
                f"{name} names {len(per_layer)} layers, num_layers is "
                f"{self.num_layers}"
            )
        if self.layer_windows is not None:
            assert all(w is None or w > 0 for w in self.layer_windows), (
                f"layer_windows entries are positive or None: "
                f"{self.layer_windows}"
            )
            assert self.attention_window is None, (
                "layer_windows gives every layer its window; "
                "attention_window is the one uniform window"
            )
            assert self.sequence_parallel_size == 1, (
                "layer_windows does not compose with ring sequence "
                "parallelism yet"
            )
        per_layer_differs = any(
            t is not None and len(set(t)) > 1
            for t in (self.layer_windows, self.layer_rope,
                      self.layer_kv_heads, self.layer_rope_theta,
                      self.layer_sink)
        )
        assert not (self.scan_layers and per_layer_differs), (
            "scan_layers needs one kind of layer: layer_windows / "
            "layer_rope / layer_kv_heads / layer_rope_theta / layer_sink "
            "differ by layer"
        )
        for n_kv in {self.num_kv_heads, *(self.layer_kv_heads or ())}:
            assert n_kv > 0 and self.num_heads % n_kv == 0, (
                f"num_heads {self.num_heads} must be divisible by the k/v "
                f"heads of every layer, got {n_kv}"
            )
        if self.layer_rope_theta is not None:
            assert all(t > 0 for t in self.layer_rope_theta), (
                f"layer_rope_theta entries are positive: "
                f"{self.layer_rope_theta}"
            )
        if self.rope_dim is not None:
            assert 0 < self.rope_dim <= self.head_dim() and (
                self.rope_dim % 2 == 0
            ), (
                f"rope_dim {self.rope_dim}: an even number of a head's "
                f"{self.head_dim()} columns"
            )
        assert self.attn_value_scale > 0, (
            f"attn_value_scale must be positive, got {self.attn_value_scale}"
        )
        assert self.attn_sink_init_std >= 0, "attn_sink_init_std is a std"
        if self.attn_value_dim is not None:
            assert 0 < self.attn_value_dim <= self.head_dim(), (
                f"attn_value_dim {self.attn_value_dim}: at most the key's "
                f"{self.head_dim()} columns (a key wider than its value is "
                "kept in value-width parts)"
            )
            assert self.sequence_parallel_size == 1, (
                "attn_value_dim does not compose with ring sequence "
                "parallelism: its chunks merge outputs of the key's width"
            )
            assert self.key_parts() == 1 or self.kv_cache_dtype != "int8", (
                "kv_cache_dtype='int8' is not served over a key kept in "
                "parts (attn_value_dim under attn_head_dim): a row's scale "
                "would span the parts"
            )
        if self.layer_sink is not None and any(self.layer_sink):
            assert self.sequence_parallel_size == 1, (
                "layer_sink does not compose with ring sequence "
                "parallelism: a chunk's partial softmax would count the "
                "sink once a shard"
            )
        assert self.precision in PRECISIONS, f"invalid precision {self.precision}"
        assert self.rope_dtype in ("fp32", "bf16"), (
            f"invalid rope_dtype {self.rope_dtype}"
        )
        assert self.kv_cache_dtype in ("bf16", "int8"), (
            f"invalid kv_cache_dtype {self.kv_cache_dtype}"
        )
        assert self.attention_backend in ("dense", "ragged_xla", "ragged"), (
            f"invalid attention_backend {self.attention_backend}"
        )
        assert self.prefill_chunk_size >= 0, (
            "prefill_chunk_size must be >= 0 (0 disables chunked prefill)"
        )
        assert self.prefix_cache_pages >= 0, (
            "prefix_cache_pages must be >= 0 (0 disables the prefix cache)"
        )
        assert self.prefix_cache_tenant_quota >= 0, (
            "prefix_cache_tenant_quota must be >= 0 (0 = unbounded)"
        )
        if self.attention_window is not None:
            assert self.attention_window > 0, (
                f"attention_window must be positive, got "
                f"{self.attention_window}"
            )
            # Composes with ring attention (r5): the ring body masks the
            # global band, skips whole out-of-band chunks, and merges the
            # far-edge straddling chunk by lse (ops/ring_attention.py).
        assert self.lr_scheduler in LR_SCHEDULES, (
            f"invalid lr_scheduler {self.lr_scheduler}"
        )
        assert self.watchdog_k > 0, "watchdog_k must be positive"
        assert self.watchdog_floor_s > 0, "watchdog_floor_s must be positive"
        assert self.watchdog_warmup >= 1, "watchdog_warmup must be >= 1"
        assert self.watchdog_poll_s > 0, "watchdog_poll_s must be positive"
        assert self.step_anomaly_k > 1, "step_anomaly_k must be > 1"
        assert self.slo_sample_interval_s > 0, (
            "slo_sample_interval_s must be positive"
        )
        assert self.slo_ring_points >= 2, "slo_ring_points must be >= 2"
        assert self.slo_max_series >= 1, "slo_max_series must be >= 1"
        assert 0 < self.slo_fast_window_s < self.slo_slow_window_s, (
            "slo windows must satisfy 0 < fast < slow"
        )
        assert self.slo_fast_burn >= 1, "slo_fast_burn must be >= 1"
        assert self.slo_slow_burn >= 1, "slo_slow_burn must be >= 1"
        assert 0 < self.slo_budget <= 1, "slo_budget must be in (0, 1]"
        assert self.slo_ttft_p95_s > 0, "slo_ttft_p95_s must be positive"
        assert self.slo_decode_p50_s > 0, "slo_decode_p50_s must be positive"
        assert 0 < self.slo_error_rate <= 1, (
            "slo_error_rate must be in (0, 1]"
        )
        assert 0 < self.slo_goodput_fraction <= 1, (
            "slo_goodput_fraction must be in (0, 1]"
        )
        assert self.slo_step_time_factor > 1, (
            "slo_step_time_factor must be > 1"
        )
        assert self.io_retries >= 1, "io_retries must be >= 1 (1 = no retry)"
        assert self.io_retry_base_s > 0, "io_retry_base_s must be positive"
        assert self.io_retry_max_s >= self.io_retry_base_s, (
            "io_retry_max_s must be >= io_retry_base_s"
        )
        if self.io_timeout_s is not None:
            assert self.io_timeout_s > 0, "io_timeout_s must be positive"
        assert self.checkpoint_verify in ("full", "sample", "off"), (
            f"invalid checkpoint_verify {self.checkpoint_verify!r} "
            "(one of full/sample/off)"
        )
        assert 0.0 < self.data_quarantine_max_rate <= 1.0, (
            "data_quarantine_max_rate must be in (0, 1]"
        )
        assert self.router_probe_interval_s > 0, (
            "router_probe_interval_s must be positive"
        )
        assert self.router_breaker_failures >= 1, (
            "router_breaker_failures must be >= 1"
        )
        assert self.router_breaker_cooldown_s > 0, (
            "router_breaker_cooldown_s must be positive"
        )
        assert self.router_max_failovers >= 0, (
            "router_max_failovers must be >= 0"
        )
        assert 0.0 <= self.router_hedge_budget <= 1.0, (
            "router_hedge_budget must be in [0, 1]"
        )
        assert self.router_hedge_max_tokens >= 1, (
            "router_hedge_max_tokens must be >= 1"
        )
        assert self.page_pull_timeout_s > 0, (
            "page_pull_timeout_s must be positive"
        )
        assert self.page_share_max_inflight >= 1, (
            "page_share_max_inflight must be >= 1"
        )
        if self.use_moe:
            assert self.moe_top_k <= self.num_experts, "moe_top_k must be <= num_experts"
            assert self.moe_pattern in MOE_PATTERNS, (
                f"invalid moe_pattern {self.moe_pattern}"
            )
            assert self.capacity_factor > 0
            assert self.moe_dispatch in (
                "sort", "gather", "einsum", "gmm", "a2a"
            ), f"invalid moe_dispatch {self.moe_dispatch}"
            if self.moe_dispatch == "a2a":
                # Cross-host expert parallelism routes tokens over the
                # 'expert' mesh axis (parallel/expert_dispatch.py): the
                # axis must exist, and the dcn tier must factor it.
                assert self.expert_parallel_size > 1, (
                    "moe_dispatch='a2a' requires an expert mesh axis "
                    "(expert_parallel_size > 1) — token routing needs "
                    "shards to route between; use 'gmm' on a single-"
                    "host/no-ep mesh"
                )
                assert (
                    self.expert_parallel_size % self.expert_dcn_size == 0
                ), (
                    f"expert_dcn_size ({self.expert_dcn_size}) must "
                    f"divide expert_parallel_size "
                    f"({self.expert_parallel_size})"
                )
                assert self.moe_a2a_overlap_chunks >= 1, (
                    "moe_a2a_overlap_chunks must be >= 1"
                )
                for name, size in (
                    ("pipeline", self.pipeline_parallel_size),
                    ("sequence", self.sequence_parallel_size),
                ):
                    assert size == 1, (
                        f"moe_dispatch='a2a' composes with data/fsdp/"
                        f"expert/tensor mesh axes only ({name}_parallel_"
                        f"size={size}); use 'gather' or 'sort' there"
                    )
                if self.tensor_parallel_size > 1:
                    assert (
                        self.intermediate_size % self.tensor_parallel_size
                        == 0
                    ), (
                        "moe_dispatch='a2a' with tensor parallelism "
                        "needs intermediate_size divisible by tensor_"
                        f"parallel_size ({self.intermediate_size} % "
                        f"{self.tensor_parallel_size})"
                    )
            assert self.expert_dcn_size >= 1, (
                "expert_dcn_size must be >= 1"
            )
            if self.moe_dispatch == "gmm":
                # The megablox grouped-matmul kernel is a Pallas custom
                # call GSPMD cannot partition, so gmm runs under shard_map
                # (models/moe.py _gmm_path): tokens shard over data/fsdp,
                # experts over 'expert', and (r6) the expert FFN dims over
                # 'tensor' — gate/up column-parallel, wo row-parallel —
                # with partial outputs psum'd over ('expert', 'tensor').
                # sequence/pipe would split the kernel's sorted row
                # dimension itself — not expressible; use 'gather' there.
                for name, size in (
                    ("pipeline", self.pipeline_parallel_size),
                    ("sequence", self.sequence_parallel_size),
                ):
                    assert size == 1, (
                        f"moe_dispatch='gmm' composes with data/fsdp/"
                        f"expert/tensor mesh axes only ({name}_parallel_"
                        f"size={size}); use 'gather' or 'sort' there"
                    )
                if self.tensor_parallel_size > 1:
                    assert (
                        self.intermediate_size % self.tensor_parallel_size
                        == 0
                    ), (
                        "moe_dispatch='gmm' with tensor parallelism needs "
                        "intermediate_size divisible by tensor_parallel_"
                        f"size ({self.intermediate_size} % "
                        f"{self.tensor_parallel_size})"
                    )
                # num_experts % expert_parallel_size is enforced by the
                # unconditional expert-parallel check below.
            assert 0.0 <= self.expert_dropout_rate <= 0.5, (
                "expert_dropout_rate must be in [0, 0.5]"
            )
            assert self.moe_score_func in ("softmax", "sigmoid"), (
                f"invalid moe_score_func {self.moe_score_func}"
            )
            plain_rule = (
                self.moe_score_func == "softmax" and self.moe_renormalize
                and not self.moe_selection_bias
                and self.moe_routed_scale == 1.0
            )
            assert plain_rule or self.moe_dispatch in (
                "sort", "gather", "gmm"
            ), (
                "moe_score_func / moe_selection_bias / moe_renormalize / "
                "moe_routed_scale other than the defaults need "
                "moe_dispatch sort, gather or gmm (einsum and a2a route "
                "by their own copies of the rule)"
            )
            assert self.num_shared_experts >= 0
            if self.experts_held is not None:
                off, cnt = self.experts_held
                assert 0 <= off and cnt >= 1 and (
                    off + cnt <= self.num_experts
                ), f"experts_held {self.experts_held} outside num_experts"
                assert self.moe_dispatch == "gmm", (
                    "experts_held runs through moe_dispatch='gmm' (the "
                    "ragged grouped matmul over the held experts' rows)"
                )
                assert self.total_mesh_size() == 1, (
                    "experts_held is one chip's share, told by the "
                    "configuration: it does not compose with a mesh yet "
                    "(an 'expert' mesh axis derives the share itself)"
                )
                assert not self.use_mod, "experts_held does not compose with MoD"
            assert self.moe_expert_act in ("swiglu", "relu2"), (
                f"invalid moe_expert_act {self.moe_expert_act}"
            )
            for name, off in (
                ("moe_expert_act", "swiglu"), ("moe_latent_size", None),
                ("moe_shared_size", None),
            ):
                assert getattr(self, name) == off or (
                    self.experts_held is not None
                ), (
                    f"{name}={getattr(self, name)!r} runs under "
                    "experts_held (moe_dispatch='gmm' over a share of the "
                    "experts): the sort, gather, einsum and a2a paths and "
                    "the unshared gmm path compute SwiGLU experts at the "
                    "hidden width"
                )
        if self.layer_ffns is not None:
            assert len(self.layer_ffns) == self.num_layers, (
                f"layer_ffns names {len(self.layer_ffns)} layers, "
                f"num_layers is {self.num_layers}"
            )
            assert set(self.layer_ffns) <= {"dense", "moe", "none"}, (
                f"invalid layer_ffns {sorted(set(self.layer_ffns))}"
            )
            assert self.use_moe or "moe" not in self.layer_ffns, (
                "layer_ffns names 'moe' layers and use_moe is off"
            )
            assert not self.scan_layers and not self.parallel_block, (
                "layer_ffns (a feed-forward kind a layer) runs an unrolled "
                "stack of sequential blocks: no scan_layers, no "
                "parallel_block"
            )
            assert self.pipeline_parallel_size == 1, (
                "layer_ffns does not compose with pipeline parallelism yet"
            )
        if self.layer_mixers is not None:
            assert len(self.layer_mixers) == self.num_layers, (
                f"layer_mixers names {len(self.layer_mixers)} layers, "
                f"num_layers is {self.num_layers}"
            )
            kinds = set(self.layer_mixers)
            assert kinds <= {
                "attention", "latent", "kda", "ssm", "ssm2", "none"
            }, f"invalid layer_mixers {sorted(kinds)}"
            if "ssm2" in kinds:
                inner = self.ssm2_inner()
                assert self.ssm2_num_heads % self.ssm2_groups == 0, (
                    f"ssm2_num_heads {self.ssm2_num_heads} is no multiple "
                    f"of ssm2_groups {self.ssm2_groups}"
                )
                assert inner % 128 == 0 or inner < 128, (
                    f"an 'ssm2' layer's inner width {inner} is neither a "
                    "multiple of 128 lanes nor under one tile"
                )
            assert not (self.scan_layers and len(kinds) > 1), (
                "scan_layers needs a stack of one mixer kind; "
                f"layer_mixers has {sorted(kinds)}"
            )
            if kinds - {"attention", "none"}:
                for name, size in (
                    ("sequence", self.sequence_parallel_size),
                    ("pipeline", self.pipeline_parallel_size),
                    ("tensor", self.tensor_parallel_size),
                ):
                    assert size == 1, (
                        f"'latent', 'kda', 'ssm' and 'ssm2' mixers do not "
                        f"compose with {name}_parallel_size={size} yet"
                    )
                assert self.attention_window is None, (
                    "'latent', 'kda' and 'ssm' mixers take no "
                    "attention_window (a 'latent' layer may rotate, "
                    "latent_rope, and is always full causal)"
                )
                assert self.kda_conv_size >= 1 and self.kda_head_dim >= 1
            assert not (
                "latent" in kinds and self.kv_cache_dtype == "int8"
            ), (
                "kv_cache_dtype='int8' does not compose with 'latent' "
                "layers: the latent entry carries no per-row scales"
            )
        if self.layer_mixers is not None and self.layer_ffns is not None:
            assert ("none", "none") not in zip(
                self.layer_mixers, self.layer_ffns
            ), (
                f"layer_mixers {self.layer_mixers} and layer_ffns "
                f"{self.layer_ffns}: a layer has neither mixer nor "
                "feed-forward"
            )
        assert self.yarn_factor is None or (
            self.latent_rope and self.yarn_factor >= 1.0
        ), (
            "yarn_factor (>= 1) scales a 'latent' layer's rotation and "
            "needs latent_rope; GQAttention's rotation takes no YaRN yet"
        )
        assert not self.latent_rope or self.qk_rope_head_dim % 2 == 0, (
            "latent_rope rotates pairs: qk_rope_head_dim must be even"
        )
        if self.use_mod:
            assert 0.0 < self.mod_capacity_factor <= 1.0, (
                "mod_capacity_factor must be in (0, 1]"
            )
            assert self.mod_capacity_schedule and all(
                0.0 < c <= 1.0 for c in self.mod_capacity_schedule
            ), (
                "mod_capacity_schedule entries must be in (0, 1] "
                f"(got {self.mod_capacity_schedule})"
            )
        if self.sequence_parallel_size > 1:
            assert self.seq_length % self.sequence_parallel_size == 0
            assert self.use_ring_attention, (
                "sequence_parallel_size > 1 requires use_ring_attention=True "
                "(without it every device re-gathers the full sequence, "
                "defeating sequence parallelism)"
            )
        assert self.loss_chunk_size > 0, "loss_chunk_size must be positive"
        assert self.remat_policy in (
            "nothing_saveable", "save_outs", "save_attn", "dots_saveable",
            "full",
        ), f"invalid remat_policy {self.remat_policy}"
        assert self.adam_mu_dtype in (None, "bf16"), (
            f"invalid adam_mu_dtype {self.adam_mu_dtype}"
        )
        assert self.adam_state_quantization in (None, "int8"), (
            f"invalid adam_state_quantization {self.adam_state_quantization}"
        )
        assert not (
            self.adam_state_quantization and self.adam_mu_dtype
        ), "adam_state_quantization supersedes adam_mu_dtype; set one"
        for axis in ("fsdp", "expert", "tensor", "sequence", "pipeline"):
            size = getattr(self, f"{axis}_parallel_size")
            assert size >= 1, f"{axis}_parallel_size must be >= 1"
        if self.pipeline_parallel_size > 1:
            assert self.pipeline_schedule in ("1f1b", "gpipe"), (
                f"invalid pipeline_schedule {self.pipeline_schedule}"
            )
            assert self.scan_layers, (
                "pipeline_parallel_size > 1 requires scan_layers=True "
                "(stages slice the stacked layer axis)"
            )
            assert self.num_layers % self.pipeline_parallel_size == 0, (
                "num_layers must divide evenly over pipeline stages"
            )
            n_micro = self.pipeline_microbatches or self.pipeline_parallel_size
            assert self.batch_size % n_micro == 0, (
                "batch_size must divide into pipeline_microbatches"
            )
            assert self.gradient_accumulation_steps == 1, (
                "pipeline parallelism replaces grad accumulation: raise "
                "pipeline_microbatches instead (same memory effect, no "
                "extra pipeline bubbles)"
            )
            # pp composes with every axis: data/fsdp/tensor are automatic
            # under the partial-manual shard_map; expert and sequence join
            # the manual region under the 1F1B schedule (tokens shard over
            # them, tiled all-to-alls / in-region ring attention — see
            # parallel/pipeline.py).
            if (
                self.expert_parallel_size > 1
                or self.sequence_parallel_size > 1
            ):
                assert self.pipeline_schedule == "1f1b", (
                    "pp x ep / pp x sp require pipeline_schedule='1f1b' "
                    "(manual expert/sequence parallelism lives in the "
                    "1F1B region)"
                )
                # MoD composes too: its BCE aux pmean's over the token
                # axes (models/mod.py apply_mod stat_pmean_axes); routing
                # is per local chunk with total capacity conserved.
            if self.expert_parallel_size > 1:
                assert (
                    self.batch_size // n_micro
                ) % self.expert_parallel_size == 0, (
                    "microbatch size must divide over expert_parallel_size "
                    "under pipeline parallelism (tokens shard over the "
                    "expert axis inside the pipe region)"
                )
        if self.expert_parallel_size > 1 and self.use_moe:
            assert self.num_experts % self.expert_parallel_size == 0, (
                "num_experts must divide evenly over expert_parallel_size"
            )

    # -- derived quantities (ref config_manager.py:234,505,572) ----------
    def head_dim(self) -> int:
        return self.attn_head_dim or self.hidden_size // self.num_heads

    def window_of(self, layer_idx: Optional[int]) -> Optional[int]:
        """The attention window of a layer (None: full causal)."""
        if self.layer_windows is None or layer_idx is None:
            return self.attention_window
        return self.layer_windows[layer_idx]

    def rope_of(self, layer_idx: Optional[int]) -> bool:
        """Whether a layer's attention rotates q and k."""
        if self.layer_rope is None or layer_idx is None:
            return self.use_rope
        return self.layer_rope[layer_idx]

    def kv_heads_of(self, layer_idx: Optional[int]) -> int:
        """The k/v heads of a layer's GQAttention."""
        if self.layer_kv_heads is None or layer_idx is None:
            return self.num_kv_heads
        return self.layer_kv_heads[layer_idx]

    def rope_theta_of(self, layer_idx: Optional[int]) -> float:
        """The rotation base of a layer's GQAttention."""
        if self.layer_rope_theta is None or layer_idx is None:
            return self.rope_theta
        return self.layer_rope_theta[layer_idx]

    def sink_of(self, layer_idx: Optional[int]) -> bool:
        """Whether a layer's softmax has a learned sink."""
        if self.layer_sink is None or layer_idx is None:
            return False
        return self.layer_sink[layer_idx]

    def value_dim(self) -> int:
        """Width of a GQAttention value head."""
        return self.attn_value_dim or self.head_dim()

    def key_parts(self) -> int:
        """Arrays a lane's entry keeps a GQAttention key in: one of the
        head's own width, or, where the value is narrower
        (attn_value_dim), ceil(head / value) of the value's width."""
        return -(-self.head_dim() // self.value_dim())

    def key_width(self) -> int:
        """Columns a lane's entry keeps of a GQAttention key head: the
        head's own, or its parts' together (the last zero-padded)."""
        parts = self.key_parts()
        return parts * self.value_dim() if parts > 1 else self.head_dim()

    def kv_row_bytes(self, layer_idx: int, itemsize: int = 2,
                     kv_cache_dtype: Optional[str] = None) -> int:
        """Bytes of k and v one token keeps in a layer's entry, as
        stored: the layer's own k/v heads, a key in parts with its
        padding, int8 codes with a float32 scale a head for k and for v."""
        cols = self.key_width() + self.value_dim()
        int8 = (kv_cache_dtype or self.kv_cache_dtype) == "int8"
        return self.kv_heads_of(layer_idx) * (
            cols + 8 if int8 else cols * itemsize
        )

    def ring_pages(self, layer_idx: int, page_size: int,
                   chunk: int) -> Optional[int]:
        """Pages a lane keeps of a layer with a window of its own in the
        slot-paged pool: the window, the chunk that is written before it
        is read, and a page of slack for where the band starts inside a
        page. None: the layer keeps whole pages."""
        if self.layer_windows is None:
            return None
        window = self.layer_windows[layer_idx]
        if window is None or self.mixer_kind(layer_idx) != "attention":
            return None
        return -(-(window + chunk) // page_size) + 1

    def ssm_inner(self) -> int:
        return self.ssm_expand * self.hidden_size

    def ssm_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.hidden_size // 16)

    def ssm2_inner(self) -> int:
        return self.ssm2_num_heads * self.ssm2_head_dim

    def ssm2_conv_width(self) -> int:
        """Channels an 'ssm2' layer's convolution runs over: x, B, C."""
        return self.ssm2_inner() + 2 * self.ssm2_groups * self.ssm_state_size

    def mixer_kind(self, layer_idx: int) -> str:
        """'attention' | 'latent' | 'kda' | 'ssm' | 'ssm2' | 'none' for a
        layer (layer_mixers)."""
        if self.layer_mixers is None:
            return "attention"
        return self.layer_mixers[layer_idx]

    def ffn_kind(self, layer_idx: int) -> str:
        """'dense' | 'moe' | 'none' for a layer: layer_ffns, or
        moe_pattern's placement where that is None."""
        if self.layer_ffns is not None:
            return self.layer_ffns[layer_idx]
        return "moe" if self.is_moe_layer(layer_idx) else "dense"

    def unserved_mixers(self) -> tuple:
        """The mixer kinds of this stack that only training runs: 'kda'
        (no delta-rule state a lane yet). 'attention', 'ssm' and 'latent'
        layers are served."""
        return tuple(sorted(
            set(self.layer_mixers or ())
            - {"attention", "ssm", "ssm2", "latent", "none"}
        ))

    def recurrent_or_latent(self) -> bool:
        """True when some layer's mixer has no serving path yet
        (unserved_mixers; the name dates from when 'latent' was one)."""
        return bool(self.unserved_mixers())

    def yarn(self) -> Optional[tuple]:
        """(factor, original max positions, beta_fast, beta_slow) of
        the latent layers' YaRN rotation, None without one."""
        if self.yarn_factor is None:
            return None
        return (float(self.yarn_factor), int(self.yarn_original_max),
                float(self.yarn_beta_fast), float(self.yarn_beta_slow))

    def _yarn_mscale(self, m: float) -> float:
        if self.yarn_factor is None or self.yarn_factor <= 1.0:
            return 1.0
        return 0.1 * m * math.log(self.yarn_factor) + 1.0

    def latent_rope_mscale(self) -> float:
        """What a 'latent' layer's cos and sin are multiplied by."""
        return (self._yarn_mscale(self.yarn_mscale)
                / self._yarn_mscale(self.yarn_mscale_all_dim))

    def latent_softmax_scale(self) -> float:
        """A 'latent' layer's score scale: (nope + rope dims)^-1/2,
        times YaRN's mscale(yarn_mscale_all_dim)^2."""
        dq = self.qk_nope_head_dim + self.qk_rope_head_dim
        return (1.0 / float(dq) ** 0.5
                * self._yarn_mscale(self.yarn_mscale_all_dim) ** 2)

    def keeps_lane_state(self) -> bool:
        """True when some layer keeps a fixed state a lane (models/ssm.py
        LaneState) and not pages of k/v: what the prefix cache, page
        pulls and speculation cannot share, copy or roll back yet."""
        return bool({"ssm", "ssm2"} & set(self.layer_mixers or ()))

    def moe_width(self) -> int:
        """Width of the rows the routed experts read and write: the
        latent's, or the hidden size."""
        return self.moe_latent_size or self.hidden_size

    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    def estimate_parameters(self) -> int:
        """Total parameter count (ref core/model.py:91 estimate_parameters)."""
        h, v, L = self.hidden_size, self.vocab_size, self.num_layers
        inter = self.intermediate_size
        embed = v * h if self.tie_word_embeddings else 2 * v * h
        d, dv, n_q = self.head_dim(), self.value_dim(), self.num_heads
        # q, o over the value's width, k, v over the layer's own k/v
        # heads, a sink's logits where the layer has one.
        attn = sum(
            h * n_q * d + n_q * dv * h
            + h * self.kv_heads_of(i) * (d + dv)
            + (n_q if self.sink_of(i) else 0)
            for i in range(L)
        )
        ffn_dense = 3 * h * inter  # gate, up, down
        per_layer_norms = 2 * h
        total = embed + attn + L * per_layer_norms + h  # final norm
        moe_layers = self.num_moe_layers()
        dense_layers = L - moe_layers
        total += dense_layers * ffn_dense
        total += moe_layers * (self.num_experts * ffn_dense + h * self.num_experts)
        if self.use_mod:
            total += L * h  # MoD routers
        return total

    def estimate_active_parameters(self) -> int:
        """Active (per-token) params (ref core/model.py:1808)."""
        total = self.estimate_parameters()
        if not self.use_moe:
            return total
        h, inter = self.hidden_size, self.intermediate_size
        ffn_dense = 3 * h * inter
        moe_layers = self.num_moe_layers()
        inactive = moe_layers * (self.num_experts - self.moe_top_k) * ffn_dense
        return total - inactive

    def num_moe_layers(self) -> int:
        if not self.use_moe:
            return 0
        return sum(1 for i in range(self.num_layers) if self.is_moe_layer(i))

    def is_moe_layer(self, layer_idx: int) -> bool:
        """MoE layer placement pattern (ref core/model.py:1545 _should_use_moe)."""
        if not self.use_moe or self.moe_pattern == "none":
            return False
        if self.layer_ffns is not None:
            return self.layer_ffns[layer_idx] == "moe"
        if self.moe_pattern == "all":
            return True
        if self.moe_pattern == "every_3rd":
            return layer_idx % 3 == 2
        if self.moe_pattern == "every_4th":
            return layer_idx % 4 == 3
        if self.moe_pattern == "sandwich":
            return (
                self.dense_start_layers <= layer_idx
                < self.num_layers - self.dense_end_layers
            )
        return False

    def memory_estimate_gb(self) -> Dict[str, float]:
        """Rough HBM footprint estimate (ref config_manager.py:572)."""
        params = self.estimate_parameters()
        bytes_per = 2 if "bf16" in self.resolve_precision() else 4
        param_gb = params * bytes_per / 1e9
        # Adam: fp32 master copy + 2 moments whose width the config picks
        # (fp32 default; bf16 mu; int8 codes + row scales ≈ 1B each).
        if self.adam_state_quantization == "int8":
            moment_bytes = 2  # mu + nu codes; scales are ~1/last_dim extra
        elif self.adam_mu_dtype == "bf16":
            moment_bytes = 6  # bf16 mu + fp32 nu
        else:
            moment_bytes = 8
        opt_gb = params * (4 + moment_bytes) / 1e9
        act_gb = (
            self.micro_batch_size
            * self.seq_length
            * self.hidden_size
            * self.num_layers
            * bytes_per
            * (2 if not self.gradient_checkpointing else 0.25)
        ) / 1e9
        total = param_gb + opt_gb + act_gb
        return {
            "parameters_gb": round(param_gb, 3),
            "optimizer_gb": round(opt_gb, 3),
            "activations_gb": round(act_gb, 3),
            "total_gb": round(total, 3),
        }

    def resolve_precision(self, for_inference: bool = False) -> str:
        p = self.inference_precision if for_inference else self.precision
        if p == "auto":
            return "bf16" if for_inference else "mixed_bf16"
        # fp16 is a CUDA legacy (ref GradScaler machinery); TPU MXUs take
        # bf16 natively with fp32 range, so fp16 modes alias to bf16.
        if p == "fp16":
            return "bf16"
        if p == "mixed_fp16":
            return "mixed_bf16"
        return p

    def total_mesh_size(self) -> int:
        return (
            max(1, self.data_parallel_size)
            * self.fsdp_parallel_size
            * self.expert_parallel_size
            * self.tensor_parallel_size
            * self.sequence_parallel_size
        )

    # -- serialization (ref config_manager.py:616,637) --------------------
    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["mesh_axes"] = list(self.mesh_axes)
        return d

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        d = self.to_dict()
        with open(path, "w") as f:
            if path.endswith((".yaml", ".yml")) and _HAS_YAML:
                yaml.safe_dump(d, f, sort_keys=False)
            else:
                json.dump(d, f, indent=2)

    @classmethod
    def load(cls, path: str) -> "Config":
        with open(path) as f:
            if path.endswith((".yaml", ".yml")) and _HAS_YAML:
                d = yaml.safe_load(f)
            else:
                d = json.load(f)
        known = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in known}
        return cls(**d)


class ConfigPresets:
    """Model-size presets following the reference's 8x-MoE pattern
    (ref config_manager.py:759). Sizes name the *active* parameter count."""

    @staticmethod
    def debug() -> Config:
        return Config(
            vocab_size=1024,
            hidden_size=128,
            num_layers=2,
            num_heads=2,
            num_kv_heads=1,
            seq_length=256,
            intermediate_size=256,
            batch_size=2,
            micro_batch_size=1,
            gradient_accumulation_steps=2,
            num_epochs=1,
            learning_rate=5e-5,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            capacity_factor=1.1,
            load_balancing_weight=0.005,
            eval_every_n_batches=50,
            save_every_n_batches=100,
            experiment_name="debug_run",
            log_level="DEBUG",
            health_check_interval=10,
            save_total_limit=3,
            gradient_checkpointing=False,
            scan_layers=False,
        )

    @staticmethod
    def debug_200m() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=768,
            num_layers=12,
            num_heads=12,
            num_kv_heads=4,
            seq_length=2048,
            batch_size=32,
            gradient_accumulation_steps=4,
            use_moe=False,
            use_mod=True,
            mod_capacity_factor=0.5,
            experiment_name="debug_200m",
        )

    @staticmethod
    def debug_300m() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=768,
            num_layers=6,
            num_heads=4,
            num_kv_heads=2,
            seq_length=1024,
            batch_size=16,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            experiment_name="debug_300m",
        )

    @staticmethod
    def moe_stress_test() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=512,
            num_layers=8,
            num_heads=8,
            num_kv_heads=4,
            seq_length=1024,
            batch_size=8,
            use_moe=True,
            num_experts=32,
            moe_top_k=2,
            capacity_factor=1.1,
            routing_noise_std=0.2,
            expert_parallel_size=1,
            experiment_name="moe_stress_test",
        )

    @staticmethod
    def b1() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=2048,
            num_layers=16,
            num_heads=16,
            num_kv_heads=4,
            seq_length=2048,
            batch_size=128,
            gradient_accumulation_steps=8,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            fsdp_parallel_size=8,
            experiment_name="b1",
        )

    @staticmethod
    def b7() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=4096,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            seq_length=2048,
            batch_size=512,
            gradient_accumulation_steps=16,
            learning_rate=1.5e-4,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            fsdp_parallel_size=8,
            expert_parallel_size=8,
            scan_layers=True,
            experiment_name="b7",
        )

    @staticmethod
    def b14() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=5120,
            num_layers=40,
            num_heads=40,
            num_kv_heads=8,
            seq_length=4096,
            batch_size=512,
            gradient_accumulation_steps=16,
            learning_rate=1.2e-4,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            fsdp_parallel_size=16,
            expert_parallel_size=8,
            scan_layers=True,
            experiment_name="b14",
        )

    @staticmethod
    def b30() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=6656,
            num_layers=48,
            num_heads=52,
            num_kv_heads=13,
            seq_length=4096,
            batch_size=1024,
            gradient_accumulation_steps=32,
            learning_rate=1e-4,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            fsdp_parallel_size=32,
            expert_parallel_size=8,
            scan_layers=True,
            experiment_name="b30",
        )

    @staticmethod
    def b50() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=8192,
            num_layers=48,
            num_heads=64,
            num_kv_heads=8,
            seq_length=4096,
            batch_size=1024,
            gradient_accumulation_steps=32,
            learning_rate=8e-5,
            use_moe=True,
            num_experts=16,
            moe_top_k=2,
            fsdp_parallel_size=32,
            expert_parallel_size=16,
            scan_layers=True,
            experiment_name="b50",
        )

    @staticmethod
    def b75() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=8192,
            num_layers=64,
            num_heads=64,
            num_kv_heads=8,
            seq_length=8192,
            batch_size=1024,
            gradient_accumulation_steps=32,
            learning_rate=7e-5,
            use_moe=True,
            num_experts=16,
            moe_top_k=2,
            fsdp_parallel_size=64,
            expert_parallel_size=16,
            use_ring_attention=True,
            sequence_parallel_size=1,
            scan_layers=True,
            experiment_name="b75",
        )

    @staticmethod
    def b100() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=10240,
            num_layers=64,
            num_heads=80,
            num_kv_heads=8,
            seq_length=8192,
            batch_size=2048,
            gradient_accumulation_steps=64,
            learning_rate=6e-5,
            use_moe=True,
            num_experts=32,
            moe_top_k=2,
            fsdp_parallel_size=64,
            expert_parallel_size=32,
            use_ring_attention=True,
            scan_layers=True,
            experiment_name="b100",
        )

    @staticmethod
    def b200() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=12288,
            num_layers=80,
            num_heads=96,
            num_kv_heads=8,
            seq_length=8192,
            batch_size=2048,
            gradient_accumulation_steps=64,
            learning_rate=5e-5,
            use_moe=True,
            num_experts=64,
            moe_top_k=2,
            fsdp_parallel_size=128,
            expert_parallel_size=64,
            use_ring_attention=True,
            scan_layers=True,
            experiment_name="b200",
        )

    @staticmethod
    def b300() -> Config:
        return Config(
            vocab_size=50304,
            hidden_size=16384,
            num_layers=80,
            num_heads=128,
            num_kv_heads=16,
            seq_length=8192,
            batch_size=4096,
            gradient_accumulation_steps=128,
            learning_rate=4e-5,
            use_moe=True,
            num_experts=64,
            moe_top_k=2,
            fsdp_parallel_size=128,
            expert_parallel_size=64,
            tensor_parallel_size=2,
            use_ring_attention=True,
            scan_layers=True,
            experiment_name="b300",
        )

    @staticmethod
    def flagship(
        n_chips: int = 1, tuned: bool = True, small: bool = False
    ) -> Config:
        """The 757M-total / 238M-active MoE that chip_smoke.py runs:
        sized to load the MXU on one v5e chip (state ~9GB of 16GB
        HBM). Batch scales with the chip count so per-chip
        load is constant. `tuned` is the flagship_tuned lever set:
        dropless megablox gmm dispatch, bf16 RoPE, save_attn remat and
        bf16 Adam mu. Not a --preset: its sizes name one chip, not a
        fleet tier."""
        levers = (
            dict(
                moe_dispatch="gmm",
                rope_dtype="bf16",
                remat_policy="save_attn",
                adam_mu_dtype="bf16",
            )
            if tuned
            else {}
        )
        return Config(
            vocab_size=32768,
            hidden_size=1024,
            num_layers=10,
            num_heads=16,
            num_kv_heads=8,
            seq_length=2048,
            batch_size=(8 if small else 16) * n_chips,
            use_moe=True,
            num_experts=8,
            moe_top_k=2,
            capacity_factor=1.25,
            load_balancing_weight=0.01,
            precision="bf16",
            use_flash_attention=True,
            gradient_checkpointing=True,
            **levers,
        )

    _PRESETS = (
        "debug",
        "debug_200m",
        "debug_300m",
        "moe_stress_test",
        "b1",
        "b7",
        "b14",
        "b30",
        "b50",
        "b75",
        "b100",
        "b200",
        "b300",
    )

    @classmethod
    def available(cls) -> List[str]:
        return list(cls._PRESETS)

    @classmethod
    def get(cls, name: str) -> Config:
        if name not in cls._PRESETS:
            raise ValueError(f"Unknown preset: {name}. Available: {cls.available()}")
        return getattr(cls, name)()

    @classmethod
    def get_preset_info(cls) -> Dict[str, Dict[str, Any]]:
        """Comparison table across presets (ref config_manager.py:1670)."""
        info = {}
        for name in cls._PRESETS:
            c = cls.get(name)
            info[name] = {
                "hidden_size": c.hidden_size,
                "num_layers": c.num_layers,
                "total_params": c.estimate_parameters(),
                "active_params": c.estimate_active_parameters(),
                "use_moe": c.use_moe,
                "num_experts": c.num_experts if c.use_moe else 0,
                "use_mod": c.use_mod,
                "seq_length": c.seq_length,
                "memory_gb": c.memory_estimate_gb()["total_gb"],
            }
        return info


class ConfigManager:
    """Create, validate, tune, persist configs (ref config_manager.py:1871)."""

    @staticmethod
    def create_config(preset: str = "b7", **overrides) -> Config:
        config = ConfigPresets.get(preset)
        config = dataclasses.replace(config, **overrides)
        return config

    @staticmethod
    def validate_config(config: Config, strict: bool = False) -> List[str]:
        """Returns a list of warnings; raises on hard errors (via validate())."""
        config.validate()
        warnings = []
        if config.batch_size % max(1, config.micro_batch_size) != 0:
            warnings.append("batch_size is not a multiple of micro_batch_size")
        if config.use_moe and config.capacity_factor < 1.0:
            warnings.append("capacity_factor < 1.0 will drop tokens aggressively")
        if config.seq_length % 128 != 0:
            warnings.append("seq_length not a multiple of 128 (TPU lane width)")
        if config.hidden_size % 128 != 0:
            warnings.append("hidden_size not a multiple of 128 (MXU tiling)")
        mem = config.memory_estimate_gb()["total_gb"]
        shards = config.fsdp_parallel_size * config.tensor_parallel_size
        if mem / max(1, shards) > 90:
            warnings.append(
                f"~{mem / max(1, shards):.0f}GB/chip estimated — exceeds v5p HBM"
            )
        if strict and warnings:
            raise ValueError("; ".join(warnings))
        return warnings

    @staticmethod
    def optimize_for_hardware(config: Config, n_devices: Optional[int] = None) -> Config:
        """Pick a mesh layout for the *detected* devices
        (ref config_manager.py:1921 optimize_for_hardware). Uses real device
        introspection (utils.environment): per-chip HBM decides how much
        model sharding (fsdp/tp) is needed; leftover devices become data
        parallelism."""
        from luminaai_tpu.utils.environment import get_device_info

        dev = get_device_info()
        n = n_devices or dev["device_count"]
        hbm_gb = dev.get("memory_per_device_gb") or 16.0
        updates: Dict[str, Any] = {}
        # Shard experts first (cheap all-to-all on ICI), then FSDP the rest.
        ep = 1
        if config.use_moe:
            ep = math.gcd(config.num_experts, n)
        remaining = n // ep
        updates["expert_parallel_size"] = ep
        updates["data_parallel_size"] = 1
        # State per chip: bf16/fp32 params + Adam moments ≈ 12 bytes/param,
        # divided across the model-sharding axes. Grow tp while one chip
        # can't hold its shard (norm+embed replicas bound fsdp's reach).
        state_gb = config.estimate_parameters() * 12 / 1e9
        shards = max(1, remaining)  # model-parallel ways left after ep
        tp = 1

        def per_chip_gb(tp_size: int) -> float:
            # ~75% of state is fsdp-shardable everywhere; ~25% (embeddings,
            # fused projections) only truly shards across tp. Monotonically
            # decreasing in tp at fixed total shards, so the loop below
            # terminates at the minimal tp that fits (or the caps).
            fsdp = max(1, shards // tp_size)
            return state_gb * (0.75 / (tp_size * fsdp) + 0.25 / tp_size)

        while (
            per_chip_gb(tp) > hbm_gb * 0.5
            and tp * 2 <= shards
            and tp < 8
            and config.num_heads % (tp * 2) == 0
        ):
            tp *= 2
        updates["tensor_parallel_size"] = tp
        updates["fsdp_parallel_size"] = shards // tp
        return dataclasses.replace(config, **updates)

    @staticmethod
    def save_config_with_metadata(config: Config, path: str) -> None:
        d = config.to_dict()
        d["_metadata"] = {
            "total_params": config.estimate_parameters(),
            "active_params": config.estimate_active_parameters(),
            "memory_estimate": config.memory_estimate_gb(),
            "framework": "luminaai_tpu",
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            if path.endswith((".yaml", ".yml")) and _HAS_YAML:
                yaml.safe_dump(d, f, sort_keys=False)
            else:
                json.dump(d, f, indent=2)
