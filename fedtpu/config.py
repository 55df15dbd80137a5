"""Typed configuration for every knob the reference hardcodes.

The reference has no config or flag system at all (SURVEY.md §5): hidden sizes
``[50, 200]`` live at FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:40,
Adam lr ``0.004`` at :44, StepLR ``(30, 0.5)`` at :46, ``rounds=300`` at :249,
the grid at hyperparameters_tuning.py:73-74, dataset filenames at
FL_CustomMLP...:216 / FL_SkLearn...:163. Every one of those literals gets a
typed, named field here, and the five BASELINE.json configs are shipped as
named presets.

All config dataclasses are frozen (hashable) so they can be passed as jit
static arguments.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple


def _candidate_csv_paths() -> Tuple[str, ...]:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return (
        os.path.join(here, "data", "balanced_income_data.csv"),
        "/root/reference/balanced_income_data.csv",
        "balanced_income_data.csv",
    )


def default_income_csv() -> Optional[str]:
    """Locate the income CSV the reference ships (its only dataset)."""
    for p in _candidate_csv_paths():
        if os.path.exists(p):
            return p
    return None


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Host-side data pipeline settings.

    Mirrors the preamble of every reference ``main()``
    (FL_CustomMLP...:216-246): CSV load -> label-encode object columns ->
    standard-scale -> train/test split with ``random_state=42``.
    """

    csv_path: Optional[str] = None       # None => synthetic income-like data
    dataset_name: Optional[str] = None   # 'cifar10' selects the image loader (fedtpu.data.cifar10); 'tokens' the synthetic federated token corpus (fedtpu.data.tokens: synthetic_rows packed sequences of synthetic_features tokens); None = tabular/CSV
    label_column: str = "income"         # FL_SkLearn...:164 ('Outcome' for the diabetes path, FL_CustomMLP...:217)
    test_size: float = 0.2               # FL_CustomMLP...:239
    split_seed: int = 42                 # random_state=42 everywhere in the reference
    scale_with_mean: bool = True         # FL_SkLearn...:184 uses with_mean=False; torch driver uses default True
    # CSV parse + label-encode via the C++ loader (fedtpu.native), falling
    # back to pandas when no toolchain is available. Parity-tested identical.
    native_loader: bool = True
    # The reference fits the scaler on the FULL dataset before splitting
    # (FL_CustomMLP...:235-236) — train/test leakage. Parity default keeps it;
    # set False for the clean fit-on-train-only pipeline.
    scaler_leakage_parity: bool = True
    synthetic_rows: int = 2048           # used when csv_path is None (tests / CI)
    synthetic_features: int = 14         # balanced_income_data.csv has 14 features + label
    synthetic_classes: int = 2


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """How the (replicated) train set is carved into per-client shards.

    The reference shards contiguously by rank with the last rank taking the
    remainder (FL_CustomMLP...:48-61). Its shuffle is an UNSEEDED per-rank
    ``np.random.permutation`` (:53) so client shards overlap instead of
    partitioning the data — a real behavioral quirk. fedtpu defaults to a
    shared-seed permutation (a true partition); ``unseeded_per_client_bug``
    reproduces the reference behavior for bit-parity experiments.
    """

    num_clients: int = 8
    shuffle: bool = True
    shard_seed: int = 0
    unseeded_per_client_bug: bool = False
    strategy: str = "contiguous"         # 'contiguous' | 'label_sort' | 'dirichlet'
    dirichlet_alpha: float = 0.5         # label-skew strength for 'dirichlet'
    # Partition view for elastic-reshard verification (docs/resilience.md):
    # > 0 shards the data as if partition_clients clients existed, then keeps
    # only rows [partition_offset, partition_offset + num_clients). A run at
    # the post-shrink topology under these flags sees bitwise the SAME
    # per-client rows (padding included) as the survivors of a live reshard
    # from partition_clients down to num_clients. 0 = off (shard normally).
    partition_clients: int = 0
    partition_offset: int = 0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Model family + shape. MLP is FL_CustomMLP...:12-25; ConvNet is the
    BASELINE.json config-5 CIFAR-10 stress model (new, no reference analogue)."""

    kind: str = "mlp"                    # 'mlp' | 'convnet' | 'olmoe' | 'nemotron_h' | 'xing4' | 'kimi_linear' | 'solar_open2' | 'phi4_flash'
    # () degenerates the MLP to a single Linear — multinomial logistic
    # regression (pinned by tests/test_round_smoke.py).
    hidden_sizes: Tuple[int, ...] = (50, 200)  # FL_CustomMLP...:40
    num_classes: int = 2
    input_dim: int = 14                  # income CSV feature count
    image_shape: Tuple[int, int, int] = (32, 32, 3)  # convnet only (HWC)
    conv_channels: Tuple[int, ...] = (32, 64)
    param_dtype: str = "float32"
    compute_dtype: str = "float32"       # set 'bfloat16' to run matmuls on the MXU in bf16
    # kind='olmoe' (fedtpu.models.olmoe): the keys of the published
    # config.json under their own names, at the values of
    # allenai/OLMoE-1B-7B-0125-Instruct. Rows are packed sequences (token
    # and segment ids) and the task is next-token prediction; num_classes,
    # input_dim and hidden_sizes mean nothing to this kind.
    hidden_size: int = 2048
    num_attention_heads: int = 16        # head_dim = hidden_size / heads = 128
    num_hidden_layers: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    intermediate_size: int = 1024        # the width of ONE expert
    vocab_size: int = 50304
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    norm_topk_prob: bool = False
    # kind='nemotron_h' (fedtpu.models.nemotron_h): the keys of the published
    # config.json of nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 under
    # their own names and at its values; it also reads hidden_size,
    # num_attention_heads, num_hidden_layers (the pattern's length),
    # num_experts_per_tok, norm_topk_prob and vocab_size above, which its
    # preset sets. One mixer a layer, chosen by the pattern's letter: M a
    # Mamba-2 mixer, E sparse experts beside a shared one, * attention.
    hybrid_override_pattern: str = "MEMEM*EME"
    layer_norm_epsilon: float = 1e-5
    mamba_num_heads: int = 64            # inner width = heads x head_dim
    mamba_head_dim: int = 64
    n_groups: int = 8                    # groups of B, C and of the gated norm
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001         # the initializer's, of dt_bias
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    head_dim: int = 128                  # attention's, not hidden / heads
    num_key_value_heads: int = 2
    n_routed_experts: int = 128          # the router's width
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    # This chip's share of every expert layer: it routes over all
    # n_routed_experts and computes experts [first_expert, first_expert +
    # experts_held) and the shared expert. 0 held = all of them.
    experts_held: int = 0
    first_expert: int = 0
    # kind='xing4' (fedtpu.models.xing4): the keys of the published
    # config.json of XingChen-AGI/Xing4.0-29B-A4B under their own names and
    # at its values (its nested ``rope_scaling`` group flat, each key behind
    # ``rope_scaling_``); it also reads hidden_size, num_attention_heads,
    # num_hidden_layers, intermediate_size (here the width of a leading
    # DENSE layer), vocab_size, rope_theta, rms_norm_eps, norm_topk_prob,
    # num_experts_per_tok, n_routed_experts, moe_intermediate_size,
    # routed_scaling_factor, experts_held and first_expert above, which its
    # preset sets. Latent attention behind two low-rank bottlenecks, a
    # residual of hc_mult streams mixed around every sublayer, the first
    # first_k_dense_replace layers a plain gated MLP and the others sparse
    # experts beside n_shared_experts shared ones, then
    # num_nextn_predict_layers multi-token-prediction modules (0 or 1).
    q_lora_rank: Optional[int] = 768     # None: the query is one projection
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_scaling_factor: float = 64.0
    rope_scaling_original_max_position_embeddings: int = 4096
    rope_scaling_beta_fast: float = 32.0
    rope_scaling_beta_slow: float = 1.0
    rope_scaling_mscale: float = 1.0
    rope_scaling_mscale_all_dim: float = 1.0
    first_k_dense_replace: int = 2
    n_shared_experts: int = 1
    num_nextn_predict_layers: int = 0       # published: 1; a preset states it
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # kind='kimi_linear' (fedtpu.models.kimi_linear): the keys of the
    # published config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct. Its
    # nested ``linear_attn_config`` group lies flat here (kda_layers,
    # full_attn_layers, both 1-based as published; kda_num_heads,
    # kda_head_dim, short_conv_kernel_size) and four of its keys go by the
    # names the expert layer already reads: num_experts -> n_routed_experts,
    # num_experts_per_token -> num_experts_per_tok, num_shared_experts ->
    # n_shared_experts, moe_renormalize -> norm_topk_prob. It also reads
    # hidden_size, num_attention_heads, num_hidden_layers, intermediate_size
    # (the leading dense layers' width), first_k_dense_replace, kv_lora_rank,
    # qk_nope_head_dim, qk_rope_head_dim, v_head_dim, q_lora_rank (None),
    # moe_intermediate_size, routed_scaling_factor, rms_norm_eps, vocab_size,
    # experts_held and first_expert above, which its preset sets. A KDA
    # mixer (a gated delta-rule recurrence, a decay a key channel) in the
    # layers of kda_layers, latent attention in those of full_attn_layers;
    # mla_use_nope: the latent attention's rotary columns are kept and
    # nothing is rotated (xing4's rotates them).
    kda_layers: Tuple[int, ...] = (1, 2, 3)
    full_attn_layers: Tuple[int, ...] = (4,)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    mla_use_nope: bool = False
    # kind='solar_open2' (the same module, fedtpu.models.kimi_linear): the
    # keys of the published config.json of upstage/Solar-Open2-250B. It
    # reads kda_num_heads, kda_head_dim and short_conv_kernel_size above (its
    # ``linear_attn_config`` group flat), hidden_size, num_attention_heads,
    # num_key_value_heads, head_dim, num_hidden_layers, first_k_dense_replace
    # (0), n_routed_experts, n_shared_experts, num_experts_per_tok,
    # norm_topk_prob, routed_scaling_factor, moe_intermediate_size,
    # rms_norm_eps, vocab_size, experts_held and first_expert, which its
    # preset sets. gqa_layers: the layers, 0-BASED as published, whose mixer
    # is grouped-query softmax attention without positions
    # (fedtpu.models.layers.attention_mixer); every other layer is a KDA
    # mixer. The stack reads this list and not the model's name: where it is
    # given the "full" layer is the grouped-query one, where it is empty the
    # two 1-based lists above name the layers and the full layer is latent
    # attention. use_gqa_gate: that layer's context times sigmoid(W_g x), a
    # number a head and channel, before W_o. kda_allow_neg_eigval: the delta
    # rule's step is 2 sigmoid(W_b x), so I - beta k k^T has eigenvalues in
    # (-1, 1].
    gqa_layers: Tuple[int, ...] = ()
    use_gqa_gate: bool = False
    kda_allow_neg_eigval: bool = False
    # kind='phi4_flash' (fedtpu.models.phi4_flash): the keys of the published
    # config.json of microsoft/Phi-4-mini-flash-reasoning (``model_type:
    # phi4flash``) under their own names; it also reads hidden_size,
    # num_attention_heads, num_key_value_heads, num_hidden_layers (the
    # PUBLISHED depth, 32: a layer's kind follows from its published index),
    # intermediate_size and vocab_size above, which its preset sets. Even
    # layers a Mamba-1 mixer (mamba_* are the family's names for the inner
    # expansion, the state, the taps and the step's rank; 0 = ceil(hidden /
    # 16)), odd layers of the first half differential attention under
    # sliding_window; layer depth / 2 keeps its scan's output as the memory,
    # the next is full attention whose keys and values are kept, and after
    # them even layers are Gated Memory Units and odd ones cross-attention.
    # LayerNorm with a bias, a gated SiLU feed-forward every layer, no
    # positions, the head tied to the embedding. layers_held: the published
    # indices of the layers this chip holds, in order (() = all of them).
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    tie_word_embeddings: bool = False
    layers_held: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    """Adam + StepLR exactly as the torch driver configures them
    (FL_CustomMLP...:44-46): Adam(lr=0.004), StepLR(step_size=30, gamma=0.5),
    scheduler stepped once per round (:73)."""

    name: str = "adam"                   # 'adam' | 'sgd'
    learning_rate: float = 0.004
    b1: float = 0.9                      # torch Adam defaults
    b2: float = 0.999
    eps: float = 1e-8
    steplr_step_size: int = 30
    steplr_gamma: float = 0.5
    momentum: float = 0.9                # sgd only


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Round orchestration: FedAvg flavor + the early-stopping machinery of
    FL_CustomMLP...:122-192."""

    rounds: int = 300                    # FL_CustomMLP...:249
    weighting: str = "data_size"         # 'data_size' (FL_CustomMLP...:112-115) | 'uniform' (hyperparameters_tuning.py:37)
    termination_patience: int = 10       # FL_CustomMLP...:122
    tolerance: float = 1e-4              # FL_CustomMLP...:122
    # Partial participation (classic FedAvg client sampling; also serves as
    # straggler/dropout fault injection). 1.0 == reference behavior: every
    # client trains every round. See fedtpu.parallel.round.
    participation_rate: float = 1.0
    participation_seed: int = 0
    # Reduction backend for the PARAMETER-AVERAGING path (the FedAvg
    # weighted sum + total-weight reduction): 'psum' (XLA-scheduled
    # collective, production) | 'ring' (explicit ppermute rotate-accumulate)
    # | 'ring-rsag' (explicit reduce-scatter + all-gather). Metric pooling
    # (confusion matrices) always uses psum — it feeds replicated host
    # output, not the averaging path. See fedtpu.parallel.ring for why the
    # ring is the ICI-native answer to the reference's rank-0
    # gather/average/bcast (FL_CustomMLP...:101-120).
    aggregation: str = "psum"
    # Classic-FedAvg local work per round. The reference does exactly ONE
    # full-batch step per round (train_one_epoch, FL_CustomMLP...:63-73);
    # local_steps=E runs E of them (epoch == step under full batch).
    local_steps: int = 1
    # FedProx proximal coefficient: mu/2 * ||w - w_round_start||^2 added to
    # each local loss. Zero gradient at the anchor, so meaningful only with
    # local_steps > 1 (bounds client drift on non-IID shards). 0 = FedAvg.
    prox_mu: float = 0.0
    # SCAFFOLD (Karimireddy et al. 2020): per-client control variates c_i
    # and their server mean c correct every local gradient by (c - c_i),
    # CANCELLING client drift instead of damping it like prox_mu — the
    # stronger fix for many local steps on non-IID shards. Variate refresh
    # is option I (gradient at the round-start global), exact under any
    # local optimizer. Requires weighting='uniform', aggregation='psum',
    # the 1-D engine; composes with local_steps, prox_mu, client sampling
    # (absentees keep stale variates — the paper's |S|/N rule), and the
    # FedOpt server optimizers; not with DP (the variates would be an
    # unaccounted release), compress, or robust rules.
    scaffold: bool = False
    # Server-side optimizer over the weighted mean of client DELTAS (FedOpt
    # family, fedtpu.ops.server_opt): 'none' (parameter averaging — the
    # reference's rule) | 'fedavgm' | 'fedadagrad' | 'fedyogi' | 'fedadam'.
    # Requires aggregation='psum'; works on BOTH engines (1-D shard_map and
    # the 2-D tensor-parallel GSPMD engine).
    server_opt: str = "none"
    server_lr: float = 1.0               # 1.0 + fedavgm momentum 0 == FedAvg
    server_momentum: float = 0.9         # fedavgm only
    server_b1: float = 0.9               # adaptive server opts
    server_b2: float = 0.99              # Reddi et al. default
    server_tau: float = 1e-3             # adaptivity floor
    # Central differential privacy on the delta path (DP-FedAvg): per-client
    # L2 clip of the update (0 = off) and Gaussian noise with std
    # noise_multiplier * clip / total_weight added to the averaged delta.
    # Use weighting='uniform' for standard sensitivity accounting.
    dp_clip_norm: float = 0.0
    dp_noise_multiplier: float = 0.0
    dp_seed: int = 0
    # Adaptive clipping (Andrew et al. 2021): the clip norm becomes server
    # state initialized at dp_clip_norm and tracking the dp_target_quantile
    # of client update norms via clip *= exp(-dp_clip_lr * (b - quantile)),
    # where b is the (noisy) clipped fraction. With DP noise on, the budget
    # splits between the delta release (effective z_delta) and the
    # unit-sensitivity count (dp_count_noise_multiplier, must be > z/2) so
    # the composition charges exactly dp_noise_multiplier per round — the
    # accountant is unchanged. With noise off it is plain quantile tracking
    # (exact fraction; count noise must be 0). 1-D engine only.
    dp_adaptive_clip: bool = False
    dp_target_quantile: float = 0.5
    dp_clip_lr: float = 0.2
    dp_count_noise_multiplier: float = 0.0
    # Target delta for the RDP accountant's (epsilon, delta) report
    # (fedtpu.ops.dp_accountant; surfaced in the run summary whenever DP
    # noise is on). Pick delta << 1/num_clients for a meaningful client-
    # level guarantee.
    dp_delta: float = 1e-5
    # Byzantine-robust aggregation: 'none' (weighted mean — the reference's
    # rule) | 'median' (coordinate-wise) | 'trimmed_mean' (drop trim_ratio
    # from each end per coordinate) | 'krum' (select the single client
    # update closest to its C - krum_f - 2 nearest peers) |
    # 'geometric_median' (smoothed Weiszfeld / RFA). Robust rules are
    # unweighted, so weighting='uniform' is required (making the semantics
    # explicit); full participation + plain psum path only.
    # byzantine_clients injects k model-poisoning clients (10x sign-flipped
    # updates) as the matching fault injection.
    robust_aggregation: str = "none"
    trim_ratio: float = 0.1
    krum_f: int = 0                      # krum's assumed malicious count
    byzantine_clients: int = 0
    # Quantized update exchange (fedtpu.parallel.compress): 'none' | 'int8'
    # — per-device weighted partial sums quantized to int8 and all-gathered.
    # Received bytes are D/8 of the exact f32 psum path's (D = devices on
    # the axis): a win for few-host DCN aggregation (2-8 hosts), the regime
    # it targets; at large D plain psum wins, hence default 'none'. Plain
    # averaging only (not server_opt/DP); aggregation='psum'; 1-D engine.
    compress: str = "none"
    # Post-training per-client personalization: E local full-batch
    # fine-tuning steps from the final global model, fresh optimizer, no
    # further averaging (fedtpu.training.personalize). 0 = off. The
    # personalized per-client metrics land in
    # ExperimentResult.personalized_metrics.
    personalize_steps: int = 0
    # Each client starts from an independent random init, matching the
    # reference where every rank constructs an unseeded torch model
    # (FL_CustomMLP...:42). Set True to start all clients identical.
    same_init: bool = False
    init_seed: int = 0
    # Warm-start every client from a saved weights artifact (the .npz the
    # sweep writes via --save-weights / save_best_weights). The reference
    # only PRINTS its grid winner (hyperparameters_tuning.py:130-132);
    # this closes the loop: sweep -> persist -> train from the winner.
    # Architecture must match; optimizer state starts fresh. When a resume
    # also applies, the checkpoint restores AFTER (and therefore over) the
    # warm start — resume continues the run, warm start only seeds new ones.
    init_weights_npz: Optional[str] = None
    # Asynchronous (FedBuff-style) federation (fedtpu.parallel.async_fed):
    # the lockstep round becomes a server TICK — each tick a
    # Bernoulli(async_arrival_rate) draw marks which clients complete,
    # completing clients train local_steps from their (possibly stale)
    # pulled anchor, and the server folds in the staleness-discounted
    # arrival mean of deltas scaled by server_lr. `rounds` counts ticks;
    # history/early-stop/checkpoint all run on tick metrics. Requires
    # weighting='uniform' (the arrival mean is unweighted), the 1-D psum
    # engine, and composes with local_steps/prox_mu; not with the sync
    # engine's sampling (arrival IS the sampling process), server_opt,
    # DP, robust rules, compress, or scaffold.
    async_mode: bool = False
    async_arrival_rate: float = 0.5      # P(client completes) per tick
    async_arrival_seed: int = 0
    async_staleness_power: float = 0.5   # delta discount (1+s)^-p; 0 = off
    # >= 2 selects true FedBuff K-buffer apply semantics: the global only
    # moves once this many updates sit in the server buffer (buffer state
    # checkpoints with the run). <= 1 applies every arrival tick.
    async_buffer_size: int = 0
    # Cohort-store engine (fedtpu.cohort; docs/scaling.md): > 0 selects
    # the streaming cohort scheduler instead of the all-clients vmap
    # engine. The population (shard.num_clients) lives in a versioned
    # ClientStateStore; each round samples cohort_size clients, streams
    # exactly their records host->device (double-buffered prefetch), and
    # writes them back — peak memory is cohort-size dependent only, flat
    # in total client count. Plain-FedAvg sync path only (the scan body
    # is the vmap round op for op — bitwise-equal when cohort ==
    # population); composition with server_opt/DP/robust/compress/
    # scaffold/async is rejected loudly.
    cohort_size: int = 0
    client_store: str = "memory"         # 'memory' | 'mmap' record backend
    # mmap backing file; None = <checkpoint_dir>/client_store.bin.
    client_store_path: Optional[str] = None
    cohort_sampling: str = "uniform"     # 'uniform' | 'weighted' | 'trace'
    cohort_seed: int = 0
    # Serving-trace file (fedtpu.serving.traces) whose arrival order
    # drives 'trace' sampling: cohorts are the next distinct users.
    cohort_trace: Optional[str] = None
    # Where a client's model lives between rounds. 'resident' (every engine
    # above): C copies of parameters and optimizer state on the clients
    # axis. 'stateless' (fedtpu.parallel.stateless): ONE global copy; each
    # round the clients train one after another from it and only their
    # weighted delta is kept, so memory does not grow with the client count
    # and a model of hundreds of millions of parameters fits. Needs
    # optim.name='sgd' with momentum 0 (a client carries nothing over).
    client_state: str = "resident"       # 'resident' | 'stateless'
    # Rows of one local SGD step. 0 = the client's whole shard, the
    # reference's full-batch step. > 0 cuts a client's epoch into
    # minibatches of this many rows, one step each (stateless engine only;
    # for the language model a row is one packed sequence).
    local_batch_rows: int = 0
    # Stateless engine: every local step runs from the client's working
    # copy, which the client's start fills from the global, so the round
    # program holds one trace of the model instead of one for each kind of
    # step the clients' counts call for (only / first / between / last): a
    # third to a quarter of a deep model's compile and executable, for one
    # more pass over the parameters a client.
    one_step_kind: bool = False
    # The reference reads its stop signal one loop-top late (:132 vs :195)
    # but the doomed iteration breaks before training — no extra round is
    # trained, so there is no lag to reproduce (tests/test_stop_lag.py
    # executes the reference to pin this; SURVEY.md §5 'race detection').


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Structured-telemetry knobs (fedtpu.telemetry): a versioned JSONL
    event sink (spans, per-round cadence, counter snapshots — read back by
    ``fedtpu report``), the startup run manifest, and the leveled logger's
    threshold. All off-path when ``events_path`` is None: the run loop then
    talks to a NullTracer and pays one no-op method call per event."""

    events_path: Optional[str] = None    # JSONL sink; None = telemetry off
    manifest: bool = True                # emit the run manifest event at start
    log_level: str = "info"              # 'debug' | 'info' | 'warning'


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Host loop I/O: logging, checkpointing, timing, held-out eval."""

    log_every: int = 1
    log_per_client: bool = False         # parity with the rank-ordered prints (FL_CustomMLP...:151-162)
    # Rounds scanned inside one compiled program (host syncs once per chunk).
    # 1 == exact reference cadence; raise for throughput when the host<->device
    # round-trip dominates (early stop may overshoot by up to R-1 rounds).
    rounds_per_step: int = 1
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0            # 0 = disabled
    # Retention: keep only the k newest complete round checkpoints, plus
    # the best-client-mean-accuracy round (always protected). 0 = keep
    # everything (a 300-round run with periodic saves otherwise keeps
    # every round_N forever — VERDICT r3 weak #4).
    keep_checkpoints: int = 0
    eval_test_every: int = 0             # 0 = disabled; reference never uses its test split (FL_CustomMLP...:243-246)
    profile_dir: Optional[str] = None    # jax.profiler trace of the round loop
    # With profile_dir set: 0 traces the whole run; K > 0 captures a
    # steady-state window — start after the first chunk (compile excluded),
    # stop at the first chunk boundary covering >= K rounds.
    profile_rounds: int = 0
    metrics_jsonl: Optional[str] = None  # append one JSON line per round
    mesh_devices: int = 0                # 0 = all visible devices
    # Failure detection (SURVEY.md §5: the reference's only failure handling
    # is a blanket `except -> comm.Abort()`, FL_CustomMLP...:203-205): halt
    # the round loop cleanly when loss or metrics go non-finite (diverged
    # run, bad lr), writing an emergency checkpoint if checkpoint_dir is set.
    halt_on_nonfinite: bool = True
    # Overlap host-side metric processing with the NEXT chunk's device
    # execution (one chunk kept in flight). Removes one dispatch+fetch round
    # trip per chunk from the critical path at the price of stop decisions
    # lagging one chunk. (The reference's
    # stop-signal bcast is also read one loop-top late — :132 vs :195 —
    # but its doomed iteration breaks before training, so unlike this
    # mode it never trains past the stop; tests/test_stop_lag.py.)
    # Default off: exact synchronous stop semantics.
    pipelined_stop: bool = False
    # MPMD round pipelining (fedtpu.orchestration.mpmd): the monolithic
    # jitted chunk decomposed into a static DAG of AOT sub-programs
    # (client-step / aggregate / metrics) with async dispatch and
    # cross-program donation, the metrics program placed on a server
    # submesh slice. Subsumes pipelined_stop (one chunk stays in flight;
    # stop decisions lag one chunk) while hiding the per-round metric
    # fetch RTT under the next chunk's client compute. Plain synchronous
    # FedAvg/FedProx path only; bitwise-identical metric history and
    # final params vs the monolithic oracle (tests/test_mpmd.py).
    mpmd: bool = False
    # >1 selects the 2-D ('clients','model') GSPMD engine
    # (fedtpu.parallel.tp): hidden weights shard over a tensor-parallel axis
    # of this extent. MLP only; partial participation unsupported there.
    model_parallel: int = 1
    # The persistent XLA cache is always on, at
    # fedtpu.compilation.resolve_cache_dir: JAX_COMPILATION_CACHE_DIR when
    # set, else this directory, else <checkout>/.jax_cache. Setting this
    # also turns on the serialized-executable ProgramCache (under the same
    # directory) for overlap_compile / mpmd / the sweep.
    compilation_cache: Optional[str] = None
    # Background-compile the rounds_per_step-wide chunk program while R=1
    # warmup rounds already train (fedtpu.compilation.CompileExecutor):
    # the same math and a shorter time-to-first-round. Bitwise the eager
    # run's results on the CPU backend (tests); on the TPU the width-1 and
    # the scanned program differ in the last bit, so the two trajectories
    # part after a few rounds (chip_smoke.py, PR 21).
    overlap_compile: bool = False
    # Structured telemetry (span/event sink, manifest, logger level).
    telemetry: TelemetryConfig = TelemetryConfig()
    # Resilience (fedtpu.resilience): deterministic fault injection — a
    # JSON file path or inline JSON string (kept as str so the config
    # stays frozen/hashable); None = no faults. See docs/resilience.md.
    fault_plan: Optional[str] = None
    # What the non-finite guard does: 'halt' (quarantine + stop, the
    # pre-resilience behavior) or 'rollback' (restore the latest good
    # checkpoint and retry — requires checkpoint_dir + checkpoint_every,
    # incompatible with pipelined_stop).
    on_divergence: str = "halt"
    # Rollback retry budget for the whole run; exhausted -> halt as today.
    rollback_retries: int = 2
    # On rollback, permanently zero the offending clients' sample masks
    # (exact weight-0 exclusion under weighting='data_size') and drop
    # their pending faults. Sync engines + data_size weighting only.
    rollback_exclude: bool = False
    # Relative parameter perturbation (leaf * (1 + scale*U[-1,1])) applied
    # from the SECOND rollback retry on — the first retry is a pure replay
    # (transient faults recover bitwise); a deterministic re-divergence
    # needs a different restart point. 0 disables.
    rollback_perturb: float = 1e-6
    # Liveness heartbeat file the loop rewrites atomically every chunk
    # (multi-process: each process writes its own derived path, see
    # fedtpu.resilience.distributed.heartbeat_path_for); monitored by
    # `fedtpu supervise`.
    heartbeat_file: Optional[str] = None
    # Collective watchdog (multi-process): abort with exit 75 when a
    # blocking host fetch / collective checkpoint stalls past this many
    # seconds — a hung peer becomes a restartable crash for the gang
    # supervisor instead of a silent deadlock. Must exceed EVERY guarded
    # phase's worst-case HEALTHY duration: both the chunk walltime
    # (compile time excluded: the watchdog only arms around blocking
    # fetches, not dispatch) and the collective checkpoint save, whose
    # duration scales with model/state size independently of chunk
    # walltime. None/0 = disabled.
    collective_timeout: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """`fedtpu serve` — the trace-driven serving front-end
    (fedtpu.serving; docs/serving.md).

    A bounded cohort of ``cohort`` engine slots absorbs an unbounded
    user population (stable user -> slot bindings with LRU eviction —
    see fedtpu.serving.engine.SlotBinder; optionally store-backed for
    true per-user identity); admitted updates become DRIVEN async
    FedBuff ticks. All admission/staleness/latency decisions run on the
    VIRTUAL clock carried by arrival timestamps, so identical trace +
    seed replays bitwise-identically."""

    host: str = "127.0.0.1"        # ingestion socket binds localhost only
    port: int = 0                  # 0 = ephemeral (see --port-file)
    cohort: int = 8                # concurrent engine slots (C)
    buffer_size: int = 0           # FedBuff K-buffer M; <= 1 applies per tick
    staleness_power: float = 0.5   # delta discount (1+s)^-p
    server_lr: float = 1.0
    local_steps: int = 1
    # Tick cadence — both may be active; 0 disables that trigger.
    tick_interval_s: float = 0.5   # virtual seconds between engine ticks
    flush_every: int = 0           # fire once this many eligible updates pend
    # Keep only the newest N per-tick history rows (0 = unbounded). The
    # history is the bitwise-determinism artifact, so it stays unbounded
    # by default; a supervised long-running server sets a window so the
    # row list (and its checkpoint) stops growing one row per tick.
    history_window: int = 0
    # Admission knobs (fedtpu.serving.admission; virtual-time units).
    rate_limit: float = 0.0        # updates/s; 0 = off
    rate_burst: float = 64.0
    max_pending: int = 0           # queue-depth backpressure cutoff; 0 = off
    stale_deprioritize: int = 4    # versions behind => deprioritize
    stale_reject: int = 16         # versions behind => reject
    # Cohort training fixture (synthetic income-shaped shards).
    data_rows: int = 256
    data_features: int = 6
    data_classes: int = 2
    model_hidden: Tuple[int, ...] = (16, 8)
    seed: int = 0
    # SLO objective on update-to-incorporation latency (virtual s) and
    # the allowed violation share. Burn = violation_share/error_budget;
    # 1.0 means the budget is consumed exactly as provisioned
    # (fedtpu.autoscale.signals.slo_burn_from_hist).
    slo_objective_s: float = 1.0
    slo_error_budget: float = 0.1
    # Sliding window (virtual s) for the admission stats the autoscale
    # control plane reads off the `stats` protocol op.
    admission_window_s: float = 10.0
    # Poisoning defense (fedtpu.robust; docs/robustness.md). screen=True
    # turns on the in-tick update screen (non-finite guard, norm-vs-
    # rolling-median, cosine-vs-server-direction); screened updates are
    # dropped before the K-buffer, counted under `admission_screened`,
    # and strike their sender — quarantine_strikes strikes quarantines
    # the user id (persisted in the cohort store when one is attached).
    screen: bool = False
    screen_norm_mult: float = 4.0    # norm > mult * rolling median => screen
    screen_cos_min: float = -0.2     # cosine vs server direction below => screen
    screen_warmup: int = 8           # accepted ticks before norm screen arms
    screen_clip_norm: float = 0.0    # L2 clip on accepted updates; 0 = off
    quarantine_strikes: int = 3      # screened strikes until quarantine


@dataclasses.dataclass(frozen=True)
class FuzzConfig:
    """`fedtpu fuzz` — compositional chaos fuzzing
    (fedtpu.resilience.fuzz; docs/resilience.md "Chaos fuzzing").

    Sizing knobs for the deterministic two-gateway campaign executor.
    Everything here is part of a campaign's replay frame: the corpus
    gate (`fedtpu check --fuzz-corpus`) replays committed campaigns
    under the DEFAULTS, so changing one legitimately regenerates the
    corpus verdict goldens."""

    budget: int = 25              # campaigns per fuzz run
    seed: int = 0                 # campaign-sampler seed
    rounds: int = 8               # traffic rounds per campaign
    users: int = 32               # user population behind the trace
    arrivals_per_round: int = 24  # trace rows per round (split by owner)
    gateways: int = 2             # fleet width (the 2-process gang)
    ckpt_every: int = 3           # checkpoint cadence (rounds)
    burn_budget: float = 8.0      # slo_burn_bounded oracle ceiling
    shrink: bool = True           # ddmin failing campaigns to reproducers


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """`fedtpu autoscale` — the SLO-driven control plane
    (fedtpu.autoscale; docs/autoscale.md).

    Thresholds are read against :class:`fedtpu.autoscale.signals.
    Snapshot` fields; the hysteresis/cooldown pair is what keeps the
    default policy from flapping (a scale signal must persist for
    ``hysteresis_ticks`` consecutive control ticks, and every action
    opens a ``cooldown_ticks`` refractory window)."""

    policy: str = "threshold"
    # SLO fold (must mirror the serving side's objective to be
    # meaningful; the simulator uses these directly).
    objective_s: float = 1.0
    error_budget: float = 0.1
    control_interval_s: float = 0.5   # snapshot cadence (virtual s live+sim)
    # Threshold knobs for the default policy.
    backlog_high: int = 256           # pending depth that means overload
    backlog_low: int = 32             # pending depth that means underload
    burn_high: float = 1.0            # SLO burn >= this is overload
    reject_high: float = 0.2          # window rate+backpressure reject share
    hysteresis_ticks: int = 2
    cooldown_ticks: int = 4
    # Actuation bounds / targets.
    min_capacity: int = 1             # gang floor (members)
    max_capacity: int = 8             # gang ceiling (members)
    cohort_high: int = 128            # set_cohort_size on scale-up
    cohort_low: int = 32              # set_cohort_size on scale-down
    tick_fast_s: float = 0.1          # set_tick_cadence on scale-up
    tick_slow_s: float = 1.0          # set_tick_cadence on scale-down

    def __post_init__(self):
        if self.objective_s <= 0 or self.error_budget <= 0:
            raise ValueError("objective_s and error_budget must be > 0")
        if self.control_interval_s <= 0:
            raise ValueError("control_interval_s must be > 0")
        if self.backlog_low > self.backlog_high:
            raise ValueError("backlog_low must be <= backlog_high")
        if self.hysteresis_ticks < 1 or self.cooldown_ticks < 0:
            raise ValueError("hysteresis_ticks >= 1 and "
                             "cooldown_ticks >= 0 required")
        if not (1 <= self.min_capacity <= self.max_capacity):
            raise ValueError("need 1 <= min_capacity <= max_capacity")
        if self.tick_fast_s <= 0 or self.tick_slow_s <= 0:
            raise ValueError("tick cadences must be > 0")


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    data: DataConfig = DataConfig()
    shard: ShardConfig = ShardConfig()
    model: ModelConfig = ModelConfig()
    optim: OptimConfig = OptimConfig()
    fed: FedConfig = FedConfig()
    run: RunConfig = RunConfig()

    def replace(self, **kw) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)


def _income_data() -> DataConfig:
    return DataConfig(csv_path=default_income_csv(), label_column="income")


# The five BASELINE.json configs as named presets (BASELINE.md config matrix).
PRESETS = {
    # 1: the reference's own CPU/mpirun baseline shape: 2 clients, 5 rounds.
    "income-2": ExperimentConfig(
        data=_income_data(),
        shard=ShardConfig(num_clients=2),
        fed=FedConfig(rounds=5),
    ),
    # 2: 8-client FedAvg MLP, one client per core on a v4-8 — the north star.
    "income-8": ExperimentConfig(
        data=_income_data(),
        shard=ShardConfig(num_clients=8),
        fed=FedConfig(rounds=300),
    ),
    # 2b: the shrink target of income-8 — the topology a live reshard lands
    # on when income-8 loses half its mesh. Audited/goldened alongside its
    # parent so a reshard can never silently change the collective schedule
    # (tests/test_audit_gate.py).
    "income-4": ExperimentConfig(
        data=_income_data(),
        shard=ShardConfig(num_clients=4),
        fed=FedConfig(rounds=300),
    ),
    # 3: sklearn MLPClassifier warm-start parity path (FL_SkLearn...),
    #    hidden (50, 400), uniform averaging, 5 rounds.
    "sklearn-parity": ExperimentConfig(
        data=dataclasses.replace(_income_data(), scale_with_mean=False),  # FL_SkLearn...:184
        shard=ShardConfig(num_clients=4),
        model=ModelConfig(hidden_sizes=(50, 400)),
        fed=FedConfig(rounds=5, weighting="uniform"),
    ),
    # 4: non-IID label-skewed income shards, 32 clients (v4-32).
    "income-32-noniid": ExperimentConfig(
        data=_income_data(),
        shard=ShardConfig(num_clients=32, strategy="dirichlet", dirichlet_alpha=0.5),
        fed=FedConfig(rounds=300),
    ),
    # 5: CIFAR-10 2-layer ConvNet, 32 clients — pmean payload stress.
    # Real CIFAR-10 when cifar-10-batches-py exists locally, synthetic
    # CIFAR-shaped data otherwise (zero-egress environments).
    "cifar10-32": ExperimentConfig(
        data=DataConfig(dataset_name="cifar10", synthetic_rows=4096),
        shard=ShardConfig(num_clients=32),
        model=ModelConfig(kind="convnet", num_classes=10,
                          hidden_sizes=(256,), compute_dtype="bfloat16"),
        fed=FedConfig(rounds=50),
    ),
}


def _olmoe(layers: int) -> ExperimentConfig:
    """allenai/OLMoE-1B-7B-0125-Instruct federated over 8 silos: synthetic
    packed 4096-token sequences (fedtpu.data.tokens), one local epoch of
    one-sequence SGD steps a round, FedAvgM on one shared global model."""
    return ExperimentConfig(
        # 16 packed sequences in all, 4,096 tokens each (the model's
        # max_position_embeddings), 1-3 a client by size skew
        data=DataConfig(dataset_name="tokens", synthetic_rows=16,
                        synthetic_features=4096),
        shard=ShardConfig(num_clients=8, shuffle=False),
        model=ModelConfig(kind="olmoe", num_hidden_layers=layers,
                          compute_dtype="bfloat16"),
        optim=OptimConfig(name="sgd", learning_rate=0.005, momentum=0.0,
                          steplr_gamma=1.0),
        fed=FedConfig(rounds=20, client_state="stateless", local_batch_rows=1,
                      server_opt="fedavgm", server_momentum=0.9,
                      same_init=True),
    )


# The published model, and the same at the depth one 16 GB chip holds (one
# layer is a whole period of the layer pattern; 625.6M of 6.92B parameters).
PRESETS["olmoe-1b-7b"] = _olmoe(16)
PRESETS["olmoe-1b-7b-l1"] = _olmoe(1)


# nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's nemotron_h tower at its
# published widths, as one 16 GB chip of a 16-way expert-parallel stage holds
# it: the first nine of its 52 layers (every kind near its published ratio),
# 8 of each layer's 128 routed experts (the router stays 128 wide, top-6) and
# an eighth of the vocabulary: 667.0M of 31.6B parameters. Federated as the
# OLMoE presets are, on 16 packed 8,192-token sequences.
PRESETS["nemotron-h-30b-a3b-l9"] = ExperimentConfig(
    data=DataConfig(dataset_name="tokens", synthetic_rows=16,
                    synthetic_features=8192),
    shard=ShardConfig(num_clients=8, shuffle=False),
    model=ModelConfig(kind="nemotron_h", hidden_size=2688,
                      num_attention_heads=32, num_hidden_layers=9,
                      hybrid_override_pattern="MEMEM*EME",
                      num_experts_per_tok=6, norm_topk_prob=True,
                      vocab_size=16384, experts_held=8, first_expert=0,
                      compute_dtype="bfloat16"),
    optim=OptimConfig(name="sgd", learning_rate=0.005, momentum=0.0,
                      steplr_gamma=1.0),
    fed=FedConfig(rounds=20, client_state="stateless", local_batch_rows=1,
                  server_opt="fedavgm", server_momentum=0.9, same_init=True),
)


# XingChen-AGI/Xing4.0-29B-A4B at its published widths, as one 16 GB chip of
# an 8-way expert-parallel stage holds it: one leading dense layer and four
# expert layers of its 40 (every layer latent attention on a four-stream
# residual), 8 of each layer's 64 routed experts (the router stays 64 wide,
# top-4), an eighth of the vocabulary, and its multi-token-prediction module
# with the second loss: 913.5M of 30.3B parameters. Federated as the other two
# language models' presets are, on 16 packed 4,096-token sequences, with one
# kind of step (``one_step_kind``): four traces of six unrolled blocks
# neither compile under the chip's memory beside 10.96 GB of engine state
# nor in a benchmark run's time (PERF.md section 6, PR 37).
PRESETS["xing4-29b-a4b-l5-mtp1"] = ExperimentConfig(
    data=DataConfig(dataset_name="tokens", synthetic_rows=16,
                    synthetic_features=4096),
    shard=ShardConfig(num_clients=8, shuffle=False),
    model=ModelConfig(kind="xing4", hidden_size=3584, num_attention_heads=32,
                      num_hidden_layers=5, first_k_dense_replace=1,
                      num_nextn_predict_layers=1,
                      intermediate_size=9216, n_routed_experts=64,
                      moe_intermediate_size=1024, num_experts_per_tok=4,
                      norm_topk_prob=True, routed_scaling_factor=2.0,
                      rms_norm_eps=1e-6, vocab_size=16384, experts_held=8,
                      first_expert=0, compute_dtype="bfloat16"),
    optim=OptimConfig(name="sgd", learning_rate=0.005, momentum=0.0,
                      steplr_gamma=1.0),
    fed=FedConfig(rounds=20, client_state="stateless", local_batch_rows=1,
                  one_step_kind=True, server_opt="fedavgm",
                  server_momentum=0.9, same_init=True),
)


# moonshotai/Kimi-Linear-48B-A3B-Instruct at its published widths, as one
# 16 GB chip of a 32-way expert-parallel stage holds it: the model's first
# five layers of 27 (the leading dense layer, then K K F K of its 3 : 1
# pattern: four KDA mixers and one latent-attention layer without positions),
# 8 of each expert layer's 256 routed experts (the router stays 256 wide,
# top-8), an eighth of the vocabulary: 602.5M parameters. Federated as the
# other language models' presets are, on 16 packed 4,096-token sequences, with
# one kind of step (PERF.md section 6, PR 39).
PRESETS["kimi-linear-48b-a3b-l5"] = ExperimentConfig(
    data=DataConfig(dataset_name="tokens", synthetic_rows=16,
                    synthetic_features=4096),
    shard=ShardConfig(num_clients=8, shuffle=False),
    model=ModelConfig(kind="kimi_linear", hidden_size=2304,
                      num_attention_heads=32, num_hidden_layers=5,
                      kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
                      first_k_dense_replace=1, intermediate_size=9216,
                      q_lora_rank=None, mla_use_nope=True,
                      rope_scaling_factor=1.0, n_routed_experts=256,
                      moe_intermediate_size=1024, num_experts_per_tok=8,
                      norm_topk_prob=True, routed_scaling_factor=2.446,
                      rms_norm_eps=1e-5, vocab_size=20480, experts_held=8,
                      first_expert=0, compute_dtype="bfloat16"),
    optim=OptimConfig(name="sgd", learning_rate=0.005, momentum=0.0,
                      steplr_gamma=1.0),
    fed=FedConfig(rounds=20, client_state="stateless", local_batch_rows=1,
                  one_step_kind=True, server_opt="fedavgm",
                  server_momentum=0.9, same_init=True),
)


# upstage/Solar-Open2-250B at its published widths, as one 16 GB chip holds
# it where 40 chips share each expert layer by experts and the four chips of
# a host each mixer by heads: the model's first four layers of 48 (one whole
# period G K K K: the gated grouped-query softmax layer without positions,
# then three KDA mixers whose step runs to 2), 8 of each layer's 320 routed
# experts (the router stays 320 wide, top-8) beside the shared one, 16 of 64
# query and KDA heads and 2 of 8 key-value heads (every head keeps its 128),
# an eighth of the vocabulary: 905.8M parameters. Federated as the other
# language models' presets are, on 16 packed 4,096-token sequences, with one
# kind of step (PERF.md section 6, PR 47).
PRESETS["solar-open2-250b-l4"] = ExperimentConfig(
    data=DataConfig(dataset_name="tokens", synthetic_rows=16,
                    synthetic_features=4096),
    shard=ShardConfig(num_clients=8, shuffle=False),
    model=ModelConfig(kind="solar_open2", hidden_size=4096,
                      num_attention_heads=16, num_key_value_heads=2,
                      head_dim=128, num_hidden_layers=4, gqa_layers=(0,),
                      use_gqa_gate=True, kda_allow_neg_eigval=True,
                      kda_num_heads=16, kda_head_dim=128,
                      first_k_dense_replace=0, intermediate_size=10240,
                      n_routed_experts=320, moe_intermediate_size=1280,
                      num_experts_per_tok=8, norm_topk_prob=True,
                      routed_scaling_factor=1.0, rms_norm_eps=1e-5,
                      vocab_size=24576, experts_held=8, first_expert=0,
                      compute_dtype="bfloat16"),
    optim=OptimConfig(name="sgd", learning_rate=0.005, momentum=0.0,
                      steplr_gamma=1.0),
    fed=FedConfig(rounds=20, client_state="stateless", local_batch_rows=1,
                  one_step_kind=True, server_opt="fedavgm",
                  server_momentum=0.9, same_init=True),
)


# microsoft/Phi-4-mini-flash-reasoning (SambaY) at its published widths, as
# one 16 GB chip of a four-stage pipeline holds it: eight of its 32 layers
# (two periods of the self-decoder, Mamba-1 and window-512 differential
# attention; the Mamba-1 layer that keeps the memory and the full attention
# that keeps keys and values; one period of the cross-decoder, a Gated Memory
# Unit and cross-attention) and a quarter of the vocabulary, the head tied to
# the embedding: 979.4M parameters. Federated as the other language models'
# presets are, on 16 packed 4,096-token sequences, with one kind of step
# (PERF.md section 6, PR 44).
PRESETS["phi4-mini-flash-l8"] = ExperimentConfig(
    data=DataConfig(dataset_name="tokens", synthetic_rows=16,
                    synthetic_features=4096),
    shard=ShardConfig(num_clients=8, shuffle=False),
    model=ModelConfig(kind="phi4_flash", hidden_size=2560,
                      num_attention_heads=40, num_key_value_heads=20,
                      num_hidden_layers=32,
                      layers_held=(0, 1, 2, 3, 16, 17, 18, 19),
                      intermediate_size=10240, sliding_window=512,
                      tie_word_embeddings=True, vocab_size=50016,
                      compute_dtype="bfloat16"),
    optim=OptimConfig(name="sgd", learning_rate=0.005, momentum=0.0,
                      steplr_gamma=1.0),
    fed=FedConfig(rounds=20, client_state="stateless", local_batch_rows=1,
                  one_step_kind=True, server_opt="fedavgm",
                  server_momentum=0.9, same_init=True),
)


def get_preset(name: str) -> ExperimentConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown preset {name!r}; available: {sorted(PRESETS)}")
    return PRESETS[name]
