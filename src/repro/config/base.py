"""Configuration dataclasses for the repro framework.

Every experiment is driven by a ``RunConfig`` composed of:
  * ``ModelConfig``    — architecture definition (block types, dims, vocab).
  * ``ParallelConfig`` — mesh layout + sharding strategy knobs.
  * ``TrainConfig``    — optimizer / schedule / checkpointing / fault tolerance.
  * ``NetConfig``      — the MatchRDMA / netsim network parameters (the paper).

All configs are frozen dataclasses so they are hashable and can key jit caches.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from dataclasses import dataclass, field
from typing import Any, Mapping, NamedTuple, Sequence

# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

# Block kinds understood by repro.models.transformer
ATTN = "attn"            # global causal GQA attention
LOCAL_ATTN = "local_attn"  # sliding-window causal attention
SSD = "ssd"              # Mamba2 state-space duality block
RGLRU = "rglru"          # RecurrentGemma RG-LRU recurrent block

MLP_SWIGLU = "swiglu"
MLP_RELU2 = "relu2"      # squared-ReLU (Nemotron-4)
MLP_GELU = "gelu"
MLP_MOE = "moe"          # top-k mixture of experts (SwiGLU experts)
MLP_NONE = "none"        # block has no separate MLP (e.g. Mamba2)


@dataclass(frozen=True)
class ModelConfig:
    """Unified decoder-only LM configuration covering all assigned families."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int                   # query heads (0 for attn-free)
    num_kv_heads: int                # KV heads (GQA); == num_heads for MHA
    d_ff: int
    vocab_size: int

    # Block pattern. If empty, every layer is (mixer=ATTN, mlp=default_mlp).
    # Otherwise a repeating pattern of (mixer_kind, mlp_kind) tuples.
    block_pattern: tuple = ()

    default_mlp: str = MLP_SWIGLU
    head_dim: int = 0                # 0 -> d_model // num_heads
    # --- attention details ---
    qkv_bias: bool = False           # Qwen1.5
    rope_theta: float = 10000.0
    local_window: int = 2048         # for LOCAL_ATTN blocks
    logit_softcap: float = 0.0       # 0 = disabled
    # --- normalization / misc ---
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act_dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25
    router_aux_loss: float = 0.01
    # group dispatch by batch rows (GShard G-groups): expert buffers gain a
    # batch-sharded leading dim, keeping dispatch/combine local to the data
    # shard — no cross-(pod,data) collectives (see EXPERIMENTS.md §Perf)
    moe_group_by_batch: bool = False
    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0               # N (state size per head)
    ssm_expand: int = 2              # d_inner = expand * d_model
    ssm_headdim: int = 64
    ssm_conv: int = 4                # depthwise conv width
    ssm_chunk: int = 128             # SSD chunk length
    # --- RG-LRU (RecurrentGemma) ---
    rglru_width: int = 0             # d_rnn (0 -> ssm_expand*d_model? use explicit)
    rglru_conv: int = 4
    # K cache stored time-minor [B, Hk, hd, S] (dot-ready layout: QK^T
    # contracts hd with S free — avoids a full-cache transpose per decode
    # step; EXPERIMENTS.md §Perf Cell A iteration 2)
    decode_k_time_minor: bool = False
    # --- modality frontend stub ---
    embed_inputs: bool = True        # False => inputs are precomputed embeddings
    # --- attention flavor for very long context ---
    subquadratic: bool = False       # True for ssm / hybrid (long_500k eligible)

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    def layer_blocks(self) -> tuple:
        """Expand block_pattern to num_layers entries of (mixer, mlp)."""
        if not self.block_pattern:
            return tuple((ATTN, self.default_mlp) for _ in range(self.num_layers))
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.num_layers))

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, v = self.d_model, self.vocab_size
        total = v * d                       # embedding
        if not self.tie_embeddings:
            total += v * d                  # unembedding
        hd = self.resolved_head_dim
        for mixer, mlp in self.layer_blocks():
            if mixer == ATTN or mixer == LOCAL_ATTN:
                q = d * self.num_heads * hd
                kv = 2 * d * self.num_kv_heads * hd
                o = self.num_heads * hd * d
                total += q + kv + o
                if self.qkv_bias:
                    total += (self.num_heads + 2 * self.num_kv_heads) * hd
            elif mixer == SSD:
                d_in = self.ssm_expand * d
                nheads = d_in // self.ssm_headdim
                # in_proj: z,x,B,C,dt ; out_proj ; conv ; A,D,dt_bias, norm
                total += d * (2 * d_in + 2 * self.ssm_state + nheads)
                total += d_in * d
                total += self.ssm_conv * (d_in + 2 * self.ssm_state)
                total += 3 * nheads + d_in
            elif mixer == RGLRU:
                w = self.rglru_width or d
                # linear in (x,y branches), gates, out
                total += d * w * 2 + w * d + 3 * w + self.rglru_conv * w + 2 * w * (w // 8 if w >= 8 else w)
            # norms
            total += 2 * d
            if mlp == MLP_SWIGLU:
                total += 3 * d * self.d_ff
            elif mlp in (MLP_RELU2, MLP_GELU):
                total += 2 * d * self.d_ff
            elif mlp == MLP_MOE:
                total += d * self.num_experts  # router
                total += self.num_experts * 3 * d * self.d_ff
        total += d  # final norm
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts)."""
        if self.num_experts == 0:
            return self.param_count()
        dense = self.param_count()
        n_moe_layers = sum(1 for _, m in self.layer_blocks() if m == MLP_MOE)
        per_expert = 3 * self.d_model * self.d_ff
        inactive = n_moe_layers * (self.num_experts - self.num_experts_per_tok) * per_expert
        return dense - inactive


# ---------------------------------------------------------------------------
# Parallelism
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParallelConfig:
    """Mesh layout + sharding strategy."""

    multi_pod: bool = False
    pods: int = 2
    data: int = 16
    model: int = 16

    fsdp: bool = False            # additionally shard params/opt-state over data axis
    remat: str = "block"          # none | block | full
    scan_layers: bool = True
    microbatches: int = 1         # gradient accumulation
    # pod-axis (inter-DC) optimizations — the MatchRDMA-motivated features
    hierarchical_allreduce: bool = True
    pod_compression: str = "none"  # none | int8
    # decode layout
    shard_cache_seq: bool = True   # shard KV-cache sequence dim over model axis
    flash_decode: bool = False     # explicit shard_map partial-softmax decode
    # optimizer state dtype (bf16 for the 340B config)
    opt_state_dtype: str = "float32"

    def axis_names(self) -> tuple:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    def mesh_shape(self) -> tuple:
        if self.multi_pod:
            return (self.pods, self.data, self.model)
        return (self.data, self.model)

    def batch_axes(self) -> tuple:
        return ("pod", "data") if self.multi_pod else ("data",)

    @property
    def num_devices(self) -> int:
        n = self.data * self.model
        return n * self.pods if self.multi_pod else n


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 256
    seq_len: int = 4096
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    seed: int = 0
    # checkpointing / fault tolerance
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 100
    ckpt_keep: int = 3
    ckpt_async: bool = True
    # straggler mitigation (simulated policy knobs)
    step_deadline_ms: float = 0.0   # 0 = disabled
    max_restarts: int = 3


# ---------------------------------------------------------------------------
# Network (the paper)
# ---------------------------------------------------------------------------

class NetParams(NamedTuple):
    """Traced per-scenario network parameters (a jax pytree).

    ``NetConfig`` stays the static, hashable side of the split: it keys jit
    caches and fixes every compile-time *shape* (``dt_us``, slot layout,
    delay-line padding). ``NetParams`` holds the scalars a scenario sweep
    varies — distance/delay, capacities, buffer thresholds — as traced f32
    leaves, so a whole distance x capacity x buffer grid can run as ONE
    ``jax.vmap``-ed computation instead of one compile per cell.

    The three ``link_*`` leaves are the per-link topology axis: shape
    ``[L]`` with ``L = cfg.num_paths`` (STATIC — it keys the compile).
    At ``L = 1`` they are present but unused: the engine takes the
    single-pipe code path, whose jaxpr the goldens pin bit-for-bit.

    Build one with ``NetParams.of(cfg)``; stack a grid with
    ``stack_net_params([cfg0, cfg1, ...])`` (leaves gain a leading [B] axis).
    """

    one_way_delay_us: Any        # f32 — long-haul one-way propagation delay
    otn_capacity_gbps: Any       # f32 — aggregate OTN line capacity
    dst_dc_gbps: Any             # f32 — destination leaf capacity
    nic_gbps: Any                # f32 — sender NIC line rate
    pfc_xoff_kb: Any             # f32 — DC-leaf PFC pause threshold
    pfc_xon_kb: Any              # f32 — DC-leaf PFC resume threshold
    otn_buffer_bdp_frac: Any     # f32 — OTN PFC headroom as a BDP fraction
    ecn_kmin_kb: Any             # f32 — ECN marking lower threshold
    ecn_kmax_kb: Any             # f32 — ECN marking upper threshold
    queue_thresh_kb: Any         # f32 — dst-OTN backlog threshold (slots)
    budget_floor_mbps: Any       # f32 — budget floor
    budget_headroom: Any         # f32 — inject <= headroom * estimated r_out
    # related-work scheme knobs (consumed only by their schemes; traced so
    # a knob grid sweeps batch-wide in one compiled launch)
    geopipe_credit_bdp_frac: Any  # f32 — geopipe segment credit window (BDP x)
    sdr_window_bdp_frac: Any     # f32 — sdr_rdma selective-repeat window (BDP x)
    sdr_ack_coalesce_us: Any     # f32 — sdr_rdma ACK coalescing interval
    sdr_retx_budget_frac: Any    # f32 — sdr_rdma rate share reserved for repair
    # channel-impairment knobs (consumed only by non-ideal channel models —
    # repro.netsim.channel; traced so impairment grids vmap jointly with
    # scheme knobs and workloads in one compiled program per scheme)
    loss_rate: Any               # f32 — stationary long-haul byte-loss frac
    loss_burst_len: Any          # f32 — mean Gilbert–Elliott burst (steps)
    jitter_us: Any               # f32 — mean stochastic extra delay
    flap_period_us: Any          # f32 — OTN protection-switch period (0=off)
    flap_depth: Any              # f32 — capacity cut inside a flap dip [0,1]
    # rdmacell flowcell-spraying knobs (consumed only by the rdmacell
    # scheme; traced so a token/ROB grid sweeps batch-wide)
    rdmacell_token_bucket_us: Any  # f32 — per-link token-bucket depth (µs
                                   # of that link's line rate)
    rdmacell_rob_limit_mb: Any     # f32 — dst reorder-buffer budget (MB)
    # traced slot length (docs/differentiable.md): steps-per-slot and the
    # control-processing delay derive from this leaf at trace time, so a
    # slot_us sweep shares ONE compiled program (the static
    # ``NetConfig.slot_us`` twin still sizes history rings).
    slot_us: Any                 # f32 — MatchRDMA slot duration (µs)
    # soft-step relaxation temperature (docs/differentiable.md): consumed
    # only when ``NetConfig.soft_step`` is True; traced so a temperature
    # anneal batches in one compile.
    soft_temp: Any               # f32 — sigmoid temperature (→0 = hard)
    # per-link topology leaves ([L], L = cfg.num_paths — static):
    link_delay_us: Any           # f32[L] — per-link one-way delay
    link_cap_gbps: Any           # f32[L] — per-link line capacity
    link_thresh_kb: Any          # f32[L] — per-link dst-OTN PFC threshold
    # trace-replay channel schedule (repro.netsim.channel trace_replay):
    # per-edge time-indexed (loss_frac, defer_frac, cap_frac) rows. The
    # VALUES are traced; the table SHAPE [L, K, 3] is static (K =
    # cfg.schedule_len keys the compile — grids sharing one schedule
    # length share one program). [L, 0, 3] = no schedule (pass-through).
    chan_schedule: Any           # f32[L, K, 3]
    chan_sched_dt_us: Any        # f32 — schedule entry duration (µs;
                                 # <= 0 means one entry per dt_us step)
    # failure schedule (repro.netsim.failures): per-edge hard-outage
    # windows. The WINDOW TIMES are traced; the window count W is static
    # shape (W = cfg.failure_len keys the compile — grids sharing one
    # window count share one program). [L, 0, 2] = no failures.
    fail_windows: Any            # f32[L, W, 2] — (down_at_us, up_at_us)

    @classmethod
    def of(cls, cfg: "NetConfig") -> "NetParams":
        import jax.numpy as jnp
        return cls(*(jnp.asarray(v) for v in _host_leaves(cfg)))

    def delay_steps(self, dt_us: float):
        """Traced step count of the long-haul delay (>= 1)."""
        import jax.numpy as jnp
        return jnp.maximum(
            jnp.round(self.one_way_delay_us / dt_us).astype(jnp.int32), 1)


def _host_leaves(cfg: "NetConfig") -> tuple:
    """One scenario's ``NetParams`` leaves, in field order, as host float32
    NumPy values: the values (and avals) ``NetParams.of`` wraps."""
    import numpy as np
    scalars = (
        cfg.one_way_delay_us, cfg.otn_capacity_gbps, cfg.dst_dc_gbps,
        cfg.nic_gbps, cfg.pfc_xoff_kb, cfg.pfc_xon_kb,
        cfg.otn_buffer_bdp_frac, cfg.ecn_kmin_kb, cfg.ecn_kmax_kb,
        cfg.queue_thresh_kb, cfg.budget_floor_mbps,
        cfg.budget_headroom, cfg.geopipe_credit_bdp_frac,
        cfg.sdr_window_bdp_frac, cfg.sdr_ack_coalesce_us,
        cfg.sdr_retx_budget_frac, cfg.loss_rate, cfg.loss_burst_len,
        cfg.jitter_us, cfg.flap_period_us, cfg.flap_depth,
        cfg.rdmacell_token_bucket_us, cfg.rdmacell_rob_limit_mb,
        cfg.slot_us, cfg.soft_temp)
    return (*(np.float32(v) for v in scalars),
            np.float32(cfg.path_delays_us()),
            np.float32(cfg.path_caps_gbps()),
            np.float32(cfg.path_pfc_kb()),
            cfg.schedule_array(),
            np.float32(cfg.channel_schedule_dt_us),
            cfg.failure_array())


def stack_net_params(cfgs: Sequence["NetConfig"]) -> NetParams:
    """Stack per-scenario params into one [B]-leading pytree for vmap.

    The leaves are host NumPy arrays, built without a device op; the
    launch puts each one on the device once."""
    import numpy as np
    lens = {c.schedule_len for c in cfgs}
    if len(lens) > 1:
        raise ValueError(
            f"stack_net_params: channel_schedule lengths differ across the "
            f"batch ({sorted(lens)}) — the [L, K, 3] schedule table is a "
            f"stacked traced leaf, so every scenario must carry the same "
            f"number of entries (pad shorter schedules)")
    wlens = {c.failure_len for c in cfgs}
    if len(wlens) > 1:
        raise ValueError(
            f"stack_net_params: failure_schedule window counts differ "
            f"across the batch ({sorted(wlens)}) — the [L, W, 2] outage "
            f"table is a stacked traced leaf, so every scenario must carry "
            f"the same number of windows (pad with no-op (0, 0) windows; "
            f"repro.netsim.failures.FailureSchedule does this)")
    return NetParams(*(np.stack(xs) for xs in
                       zip(*(_host_leaves(c) for c in cfgs))))


# NetConfig fields whose values reach the batched step ONLY through the
# traced NetParams leaves — free to vary per scenario. Every OTHER field is
# compile-time structure (dt/slot layout, DCQCN constants, ECN pmax, ...)
# and must be identical across a batch; ``batch_template`` resets the traced
# ones to the class defaults so two grids of equal shape share one compiled
# program.
NET_TRACED_FIELDS = ("distance_km", "num_otn_links", "link_gbps",
                     "dst_dc_gbps", "nic_gbps", "pfc_xoff_kb", "pfc_xon_kb",
                     "otn_buffer_bdp_frac", "ecn_kmin_kb", "ecn_kmax_kb",
                     "queue_thresh_kb", "budget_floor_mbps",
                     "budget_headroom", "geopipe_credit_bdp_frac",
                     "sdr_window_bdp_frac", "sdr_ack_coalesce_us",
                     "sdr_retx_budget_frac", "loss_rate", "loss_burst_len",
                     "jitter_us", "flap_period_us", "flap_depth",
                     "rdmacell_token_bucket_us", "rdmacell_rob_limit_mb",
                     "slot_us", "soft_temp",
                     "path_delay_scale", "path_cap_frac", "path_thresh_kb",
                     "channel_schedule", "channel_schedule_dt_us",
                     "failure_schedule")


def batch_template(cfgs: Sequence["NetConfig"]) -> "NetConfig":
    """The static template keying a batch's jit cache entry: the shared
    non-traced fields, with every NetParams-covered field reset to its
    class default (after the reset all batch members yield the same
    template, so any member serves). A non-traced field varying across the
    batch is an error: it would otherwise be silently overwritten by the
    template's value for every cell."""
    for fld in dataclasses.fields(NetConfig):
        if fld.name in NET_TRACED_FIELDS:
            continue
        vals = {getattr(c, fld.name) for c in cfgs}
        if len(vals) > 1:
            raise ValueError(
                f"simulate_batch: NetConfig.{fld.name} must be identical "
                f"across the batch (got {sorted(vals)}) — it is compile-time "
                f"structure, not a traced NetParams leaf")
    defaults = {f.name: f.default for f in dataclasses.fields(NetConfig)}
    return dataclasses.replace(
        cfgs[0], **{f: defaults[f] for f in NET_TRACED_FIELDS})


@dataclass(frozen=True)
class NetConfig:
    """MatchRDMA / netsim parameters. Defaults follow the paper's Fig. 3 setup."""

    # topology
    num_otn_links: int = 16
    link_gbps: float = 100.0              # per OTN link
    intra_dc_delay_us: float = 1.0        # one-way
    distance_km: float = 100.0            # inter-DC distance
    dst_dc_gbps: float = 400.0            # destination leaf capacity (shared w/ intra traffic)
    nic_gbps: float = 400.0               # server NIC line rate
    # multi-path long haul (docs/topology.md). ``num_paths`` is STATIC —
    # it fixes the [L] link-axis shape and keys the compile; at the default
    # 1 the engine takes the single-pipe path the goldens pin bit-for-bit.
    # The per-path tuples are traced values (length 0 or num_paths; () =
    # the symmetric default): delay multipliers on one_way_delay_us,
    # capacity fractions of otn_capacity_gbps (default: equal split), and
    # per-path dst-OTN PFC thresholds (default: pfc_xoff_kb).
    num_paths: int = 1
    path_delay_scale: tuple = ()
    path_cap_frac: tuple = ()
    path_thresh_kb: tuple = ()
    # multi-SITE graph (docs/sites.md). ``num_sites`` is STATIC; each of
    # the ``num_paths`` links is a directed site-pair EDGE: ``site_edges``
    # is () (= every link connects site 0 -> 1, the legacy single pair) or
    # a length-num_paths tuple of (src_site, dst_site) pairs. A flow only
    # sprays onto edges matching its (src_site, dst_site) endpoints
    # (``FlowSpec``); at the defaults the engine emits the identical
    # program it emitted before sites existed (goldens pin this).
    num_sites: int = 2
    site_edges: tuple = ()
    # trace-replay channel schedule (docs/channel-models.md): a recorded
    # per-edge impairment timeline for the ``trace_replay`` channel model.
    # () = no schedule, or a length-num_paths tuple of per-edge entry
    # tuples, each entry a (loss_frac, defer_frac, cap_frac) triple
    # covering ``channel_schedule_dt_us`` of simulated time (<= 0 = one
    # entry per dt_us step; the schedule loops past its end). The VALUES
    # are traced NetParams leaves; the entry count K is static shape.
    channel_schedule: tuple = ()
    channel_schedule_dt_us: float = 0.0
    # hard-failure schedule (docs/failures.md): link/site outage timelines
    # for the ``repro.netsim.failures`` subsystem. () = no failures, or a
    # length-num_paths tuple of per-edge window tuples, each window a
    # (down_at_us, up_at_us) pair during which that link is DEAD (zero
    # capacity, in-flight bytes dumped into the retransmit path). All
    # edges carry the same window count W (pad with no-op (0, 0) windows —
    # ``FailureSchedule`` builds/pads these). The window TIMES are traced
    # NetParams leaves; W is static shape keying the compile.
    failure_schedule: tuple = ()

    # simulation
    dt_us: float = 5.0                    # fluid integration step
    horizon_us: float = 100_000.0         # simulated time

    # DCQCN (values follow Zhu et al. SIGCOMM'15 conventions)
    ecn_kmin_kb: float = 200.0
    ecn_kmax_kb: float = 1600.0
    ecn_pmax: float = 0.2
    dcqcn_g: float = 1.0 / 256.0
    dcqcn_rai_mbps: float = 300.0         # additive increase
    dcqcn_hai_mbps: float = 1500.0        # hyper increase
    dcqcn_alpha_timer_us: float = 55.0
    dcqcn_rate_timer_us: float = 300.0    # rate-increase timer
    dcqcn_bytes_counter_mb: float = 10.0
    cnp_interval_us: float = 50.0         # min CNP spacing per flow
    min_rate_mbps: float = 100.0

    # PFC
    pfc_xoff_kb: float = 2048.0           # pause threshold (DC leaf switches)
    pfc_xon_kb: float = 1024.0
    # OTN nodes carry long-haul BDP: their PFC headroom scales with 2D
    otn_buffer_bdp_frac: float = 0.10     # xoff_otn = max(xoff, frac*C_otn*2D)

    # MatchRDMA controller
    slot_us: float = 100.0                # slot duration (Fig. 2e)
    slots_per_window: int = 8             # consecutive slots aggregated
    ack_delay_thresh_us: float = 20.0     # slot congestion classification
    cnp_freq_thresh: float = 0.5          # CNPs/slot threshold
    queue_thresh_kb: float = 256.0        # local dst-OTN backlog threshold
    stable_cv_thresh: float = 0.15        # coefficient-of-variation gate
    stable_weight: float = 4.0            # weight of stable recurrent windows
    jitter_weight: float = 1.0            # conservative weight of jittery slots
    budget_headroom: float = 0.98         # inject at <= headroom * estimated r_out
    budget_probe: float = 1.10            # clear-regime probe factor per ctrl window
    budget_floor_mbps: float = 500.0
    control_proc_slots: int = 1           # OTN processing delay (slots)

    # Related-work scheme knobs (traced NetParams leaves — sweep batch-wide).
    # GeoPipe-style lossless pipeline shaping: the source OTN may hold at
    # most frac x (2D x C_otn) bytes outstanding toward the destination
    # segment (credits return with one-way delay D; 1.0 is exactly
    # rate-sustaining at line rate). The default provisions the window
    # WITHIN the segment buffer (< otn_buffer_bdp_frac), so pacing stays
    # PFC-free: the credit gate, not a pause frame, is the backpressure.
    geopipe_credit_bdp_frac: float = 0.08
    # SDR-RDMA-style software-defined reliability: per-flow selective-repeat
    # receive window as a BDP fraction, receiver ACK-coalescing interval,
    # and the sender rate share reserved for repair traffic under loss
    # (scaled by the observed congestion level).
    sdr_window_bdp_frac: float = 1.0
    sdr_ack_coalesce_us: float = 50.0
    sdr_retx_budget_frac: float = 0.05
    # RDMACell-style flowcell spraying (traced NetParams leaves, consumed
    # only by the `rdmacell` scheme): per-link token-bucket depth in µs of
    # that link's line rate, and the destination reorder-buffer budget the
    # sender gate keeps occupancy under (docs/topology.md).
    rdmacell_token_bucket_us: float = 50.0
    rdmacell_rob_limit_mb: float = 64.0

    # Channel-impairment knobs (traced NetParams leaves — an impairment
    # grid sweeps batch-wide in one compiled program per scheme). Only
    # non-ideal channel models (repro.netsim.channel) consume them; the
    # defaults describe a perfect pipe, so the `ideal` channel and a zeroed
    # lossy channel are bit-identical.
    loss_rate: float = 0.0        # stationary fraction of long-haul bytes lost
    loss_burst_len: float = 1.0   # mean Gilbert–Elliott Bad dwell (steps);
                                  # 1.0 degenerates to i.i.d. Bernoulli
    jitter_us: float = 0.0        # mean stochastic extra one-way delay
    flap_period_us: float = 0.0   # OTN protection-switch period (0 = off)
    flap_depth: float = 0.0       # long-haul capacity cut inside a dip [0,1]
    channel_seed: int = 0         # static PRNG seed of the impairment draws
                                  # (counter-based: folded with the scan step)

    # Differentiable engine (docs/differentiable.md). ``soft_step`` is
    # STATIC structure: True swaps every knob-dependent hard select in the
    # step function for a sigmoid-tempered blend so jax.grad flows through
    # the scan; False emits the untouched hard jaxpr the goldens pin.
    # ``soft_temp`` is the traced temperature leaf (→ 0 recovers the hard
    # gates); ``remat_steps`` > 0 checkpoints the metrics-mode scan in
    # blocks of that many steps so reverse-mode AD over long horizons
    # stays in memory (0 = off; forward values are unchanged either way).
    soft_step: bool = False
    soft_temp: float = 1.0
    remat_steps: int = 0

    # Observability (docs/observability.md). Both STATIC — they size scan
    # carries, so they key the compile and must match across a batch.
    # ``event_ring_slots`` > 0 carries a bounded per-scenario event ring
    # through the scan (``trace_mode="window"`` only): discrete events
    # (PFC edges, threshold crossings, retx onset, failure entry/exit,
    # ``Scheme.emit_events``) are timestamped in O(E) device memory; 0 (the
    # default) emits the exact pre-obs jaxpr. ``trace_window_steps`` is the
    # ring length W of the windowed trace carry — ``trace_mode="window"``
    # keeps the LAST W steps of every trace key in O(W) memory.
    event_ring_slots: int = 0
    trace_window_steps: int = 256

    @property
    def one_way_delay_us(self) -> float:
        # 5 µs per km (paper: 1 km -> 5 µs ... 1000 km -> 5 ms)
        return 5.0 * self.distance_km

    @property
    def otn_capacity_gbps(self) -> float:
        return self.num_otn_links * self.link_gbps

    # -- per-path topology (the [L] link axis; L = num_paths, static) ------
    def _path_tuple(self, vals: tuple, default: float, what: str) -> tuple:
        if len(vals) not in (0, self.num_paths):
            raise ValueError(
                f"NetConfig.{what}: expected {self.num_paths} entries "
                f"(num_paths) or an empty tuple, got {len(vals)}")
        return tuple(float(v) for v in vals) if vals \
            else (default,) * self.num_paths

    def path_delays_us(self) -> tuple:
        """Per-path one-way delays (µs), length ``num_paths``."""
        scales = self._path_tuple(self.path_delay_scale, 1.0,
                                  "path_delay_scale")
        return tuple(self.one_way_delay_us * s for s in scales)

    def path_caps_gbps(self) -> tuple:
        """Per-path line capacities (Gbps); the default splits the
        aggregate OTN capacity equally, so L equal paths carry exactly the
        single pipe's total."""
        fracs = self._path_tuple(self.path_cap_frac, 1.0 / self.num_paths,
                                 "path_cap_frac")
        return tuple(self.otn_capacity_gbps * f for f in fracs)

    def path_pfc_kb(self) -> tuple:
        """Per-path dst-OTN PFC thresholds (KB; default pfc_xoff_kb)."""
        return self._path_tuple(self.path_thresh_kb, self.pfc_xoff_kb,
                                "path_thresh_kb")

    # -- multi-site graph (edges over the link axis; docs/sites.md) --------
    def edge_pairs(self) -> tuple:
        """Resolved per-link (src_site, dst_site) pairs, length
        ``num_paths``. The default () wires every link as the legacy
        0 -> 1 site pair. Validates the graph: site indices in range,
        no self-edges."""
        if self.num_sites < 2:
            raise ValueError(
                f"NetConfig.num_sites must be >= 2, got {self.num_sites}")
        if not self.site_edges:
            return ((0, 1),) * self.num_paths
        if len(self.site_edges) != self.num_paths:
            raise ValueError(
                f"NetConfig.site_edges: expected {self.num_paths} "
                f"(num_paths) directed (src, dst) pairs or an empty tuple, "
                f"got {len(self.site_edges)}")
        pairs = []
        for e in self.site_edges:
            if len(e) != 2:
                raise ValueError(
                    f"NetConfig.site_edges: each edge is a (src_site, "
                    f"dst_site) pair, got {e!r}")
            s, d = int(e[0]), int(e[1])
            if not (0 <= s < self.num_sites and 0 <= d < self.num_sites):
                raise ValueError(
                    f"NetConfig.site_edges: edge ({s}, {d}) references a "
                    f"site outside [0, {self.num_sites})")
            if s == d:
                raise ValueError(
                    f"NetConfig.site_edges: self-edge ({s}, {d}) — a link "
                    f"must connect two distinct sites")
            pairs.append((s, d))
        return tuple(pairs)

    @property
    def is_multisite(self) -> bool:
        """True when the config declares a genuine site graph (more than
        two sites, or explicit edge wiring). At False the engine takes the
        legacy single-pair path — bit-identical to the pre-sites
        programs the goldens pin."""
        return self.num_sites > 2 or bool(self.site_edges)

    # -- trace-replay schedule (docs/channel-models.md) --------------------
    @property
    def schedule_len(self) -> int:
        """Static entry count K of the channel schedule (0 = none).
        Validates the nested tuple: one per-edge timeline per link, all of
        equal length, each entry a (loss_frac, defer_frac, cap_frac)
        triple."""
        if not self.channel_schedule:
            return 0
        if len(self.channel_schedule) != self.num_paths:
            raise ValueError(
                f"NetConfig.channel_schedule: expected {self.num_paths} "
                f"(num_paths) per-edge timelines or an empty tuple, got "
                f"{len(self.channel_schedule)}")
        lens = {len(edge) for edge in self.channel_schedule}
        if len(lens) > 1:
            raise ValueError(
                f"NetConfig.channel_schedule: per-edge timelines differ in "
                f"length ({sorted(lens)}) — pad them to a common K")
        for edge in self.channel_schedule:
            for entry in edge:
                if len(entry) != 3:
                    raise ValueError(
                        f"NetConfig.channel_schedule: each entry is a "
                        f"(loss_frac, defer_frac, cap_frac) triple, got "
                        f"{entry!r}")
        return lens.pop() if lens else 0

    def schedule_array(self):
        """The schedule as an f32 [L, K, 3] numpy table (the traced
        ``NetParams.chan_schedule`` leaf; [L, 0, 3] when unset)."""
        import numpy as np
        k = self.schedule_len
        if k == 0:
            return np.zeros((self.num_paths, 0, 3), np.float32)
        return np.asarray(self.channel_schedule, np.float32)

    # -- failure schedule (docs/failures.md) -------------------------------
    @property
    def failure_len(self) -> int:
        """Static window count W of the failure schedule (0 = none).
        Validates the nested tuple: one per-edge window list per link, all
        of equal length, each window a (down_at_us, up_at_us) pair. A
        window with up <= down is a no-op (the padding convention)."""
        if not self.failure_schedule:
            return 0
        if len(self.failure_schedule) != self.num_paths:
            raise ValueError(
                f"NetConfig.failure_schedule: expected {self.num_paths} "
                f"(num_paths) per-edge window lists or an empty tuple, got "
                f"{len(self.failure_schedule)}")
        lens = {len(edge) for edge in self.failure_schedule}
        if len(lens) > 1:
            raise ValueError(
                f"NetConfig.failure_schedule: per-edge window lists differ "
                f"in length ({sorted(lens)}) — pad with no-op (0, 0) "
                f"windows to a common W (FailureSchedule does this)")
        for li, edge in enumerate(self.failure_schedule):
            for win in edge:
                if len(win) != 2:
                    raise ValueError(
                        f"NetConfig.failure_schedule: edge {li}: each "
                        f"window is a (down_at_us, up_at_us) pair, got "
                        f"{win!r}")
                d, u = float(win[0]), float(win[1])
                if d < 0.0 or u < 0.0:
                    raise ValueError(
                        f"NetConfig.failure_schedule: edge {li}: window "
                        f"times must be >= 0, got ({d}, {u})")
        return lens.pop() if lens else 0

    def failure_array(self):
        """The outage windows as an f32 [L, W, 2] numpy table (the traced
        ``NetParams.fail_windows`` leaf; [L, 0, 2] when unset)."""
        import numpy as np
        w = self.failure_len
        if w == 0:
            return np.zeros((self.num_paths, 0, 2), np.float32)
        return np.asarray(self.failure_schedule, np.float32)

    @property
    def control_proc_steps(self) -> int:
        """Control-subchannel OTN processing delay in fluid steps — the one
        definition every control channel (budget, credit grants) sizes its
        delay line with."""
        return int(self.control_proc_slots * self.slot_us / self.dt_us)

    @property
    def static_delay_steps(self) -> int:
        """STATIC one-way-delay step count — the one definition every
        delay-ring allocation shares. Uses the same f32 arithmetic as the
        traced ``NetParams.delay_steps`` so a static ring size can never
        undercut the traced wrap index (f64 here could round 3.4999...
        down where the f32 leaf rounds up — the ring would then be written
        through a clamped out-of-range index). With ``num_paths > 1`` this
        is the MAX over the per-path delays, so one ring allocation covers
        every link's wrap index."""
        import numpy as np
        return max(max(int(np.round(np.float32(d) / np.float32(self.dt_us)))
                       for d in self.path_delays_us()), 1)

    def horizon_steps(self, horizon_us: float = None) -> int:
        """Scan length for a horizon (default: this config's) — the single
        definition both ``simulate`` and ``simulate_batch`` size their scans
        (and warm-up cutoffs) with."""
        h = self.horizon_us if horizon_us is None else horizon_us
        return int(round(h / self.dt_us))

    def params(self) -> NetParams:
        """The traced per-scenario side of the static/traced split."""
        return NetParams.of(self)


# ---------------------------------------------------------------------------
# Run = everything
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    net: NetConfig = field(default_factory=NetConfig)

    def fingerprint(self) -> str:
        blob = json.dumps(dataclasses.asdict(self), sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# Input shapes (the assigned shape grid)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode | long_decode


SHAPES: Mapping[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "long_decode"),
}


def shape_applicable(model: ModelConfig, shape: ShapeSpec) -> bool:
    """long_500k only for sub-quadratic archs (see DESIGN.md §4)."""
    if shape.kind == "long_decode":
        return model.subquadratic
    return True
