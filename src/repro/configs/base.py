"""Configuration system: model / parallelism / run-shape configs + registry.

Every assigned architecture registers a ``ModelConfig`` (exact public
hyper-parameters) plus a reduced ``smoke`` twin for CPU tests. Input shapes
(the 4 assigned cells) are ``ShapeConfig``s; ``input_specs`` derives
ShapeDtypeStruct stand-ins for the dry-run.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp

# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | mla_moe | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0
    experts_per_tok: int = 0
    n_shared_experts: int = 0
    shared_expert_ff: int = 0
    first_dense_layers: int = 0  # deepseek: layer 0 is dense
    dense_ff: int = 0  # dense FFN width (first layers / arctic residual)
    dense_residual: bool = False  # arctic: dense FFN parallel to MoE
    moe_every: int = 1  # MoE every k-th layer (jamba: 2)
    capacity_factor: float = 1.25
    # --- MLA (deepseek) ------------------------------------------------------
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- gemma2 --------------------------------------------------------------
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    sliding_window: int = 0
    local_global_period: int = 0  # alternate local/global attention every k
    # --- hybrid / ssm ---------------------------------------------------------
    attn_layer_period: int = 0  # jamba: 1 attention layer per period
    attn_layer_offset: int = 0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    rwkv_head_dim: int = 64
    # --- frontend --------------------------------------------------------------
    frontend: str = "none"  # none | audio | vision
    # --- quantization (the paper's technique) -----------------------------------
    ternary: bool = True
    act_bits: int = 8
    # KV-cache residency dtype (attention mixers only). "int8" stores the
    # cache as int8 with per-(slot, head, row) f32 absmax scales — the
    # paper's QDQ unit applied to the cache stream, halving attention-phase
    # HBM bytes. "bf16" (default) keeps every pre-existing path bit-identical.
    kv_cache_dtype: str = "bf16"  # bf16 | int8
    # KV-cache residency *layout* (DESIGN.md §paged-kv). "contiguous" keeps
    # per-slot [B, HK, S, D] rows (every pre-existing path, bit-identical).
    # "paged" stores K/V in a device-resident page pool [P, HK, ps, D] (int8
    # scale side arrays page along) addressed through a per-slot page table —
    # a ServingEngine concern only: generate()/forward stay contiguous.
    kv_layout: str = "contiguous"  # contiguous | paged
    kv_page_size: int = 64  # tokens per page; must divide prefill_chunk_sizes[0]
    kv_num_pages: int = 0   # pool size; 0 = auto (slots * cache_len / page_size)
    # Radix-style shared-prefix reuse at admission (paged layout only):
    # full prompt pages are interned in a trie so later requests sharing a
    # system prompt map those pages read-only and prefill only the tail.
    prefix_cache: bool = True
    # Ternary matmul engine (DESIGN.md §table-lookup). "packed" pins the
    # 2-bit-planar Pallas kernels; "tl" forces the table-lookup engine
    # (paper's Algorithm 1: grouped activation tables + index gather);
    # "auto" resolves per matmul shape from the autotuner's measured
    # TL-vs-packed timings, falling back to packed when never benchmarked.
    matmul_engine: str = "auto"  # packed | tl | auto
    # --- serving: chunked prefill / continuous batching --------------------------
    # Prompts are split into chunks drawn from this grid (each size must divide
    # every larger one), so the engine compiles exactly len(sizes) prefill
    # shapes — ever. The budget caps chunk-tokens processed per scheduler tick
    # alongside the decode step, bounding decode stall under concurrent prefill.
    prefill_chunk_sizes: tuple = (64, 128, 256)
    prefill_chunk_budget: int = 512
    # --- serving: speculative decoding (DESIGN.md §speculative) ------------------
    # γ tokens drafted per decoding slot and verified in one chunked forward
    # through the prefill_append path; model-free prompt-lookup ("ngram")
    # drafting matches the longest n-gram suffix (n ≤ spec_ngram_max) of the
    # slot's prompt+emitted history against itself and proposes the
    # continuation. Off by default — ServingEngine(speculative=True) opts in.
    spec_gamma: int = 4
    spec_draft: str = "ngram"  # DRAFTERS registry key (serving/speculative.py)
    spec_ngram_max: int = 3
    # --- serving: resilience (DESIGN.md §resilience) -----------------------------
    # Bounded admission queue (0 = unbounded; submit() rejects with FAILED /
    # "queue_full" past the cap) and a default per-request TTL in seconds
    # (0 = none; Request.deadline_s overrides). Speculative ticks auto-disable
    # once >= spec_disable_after tokens have been drafted with an aggregate
    # acceptance rate below spec_min_acceptance — collapsed acceptance means
    # each γ+1-row verify forward is pure overhead.
    admission_queue_cap: int = 0
    request_ttl_s: float = 0.0
    spec_min_acceptance: float = 0.05
    spec_disable_after: int = 64
    # Engine event-log ring size (0 = unbounded): stats() bookkeeping on a
    # long-lived server stays fixed-size, with a dropped-events counter.
    stats_ring_events: int = 4096
    # Span ring of the serving path's tracer (serving/tracing.py, DESIGN.md
    # §tracing): the newest spans, with a dropped-records counter; 0 turns
    # recording off. A run of the busiest benchmark cell (set-up plus a 51 s
    # window, ~7.5 ticks/s at ~8 spans each) records ~3,200 spans: 16384
    # holds it five times over.
    trace_ring_spans: int = 16384
    # --- serving: SLO classes + replica pool (DESIGN.md §replica-pool) -----------
    # Admission classes for the multi-replica pool: (name, priority,
    # default_deadline_s, chunk_budget_weight) tuples — tuple-of-tuples so the
    # frozen config stays hashable. The class maps onto the PR-7 lifecycle
    # fields (priority feeds preemption + queue order, deadline_s the TTL; a
    # 0.0 deadline means "none — fall back to request_ttl_s") and its weight
    # scales the engine's per-tick prefill_chunk_budget while a request of
    # that class is the highest class mid-prefill: interactive prefills at
    # full pace, batch/best_effort yield tick capacity to co-batched decode.
    slo_classes: tuple = (
        ("interactive", 2, 0.0, 1.0),
        ("batch", 1, 0.0, 0.5),
        ("best_effort", 0, 0.0, 0.25),
    )
    # Replica-pool health gating / failover (serving/pool.py). A replica is
    # drained + quarantined (never hard-removed) after pool_health_fail_ticks
    # consecutive failed engine ticks or a dense straggler window
    # (pool_straggler_events flagged among the last pool_straggler_window
    # ticks); reinstatement probes run after an exponential backoff
    # (pool_backoff_s doubling to pool_backoff_max_s). A driver thread whose
    # heartbeat goes stale for pool_hang_timeout_s is declared hung and its
    # live requests are migrated like a crash.
    pool_replicas: int = 2
    pool_health_fail_ticks: int = 3
    pool_backoff_s: float = 0.25
    pool_backoff_max_s: float = 8.0
    pool_hang_timeout_s: float = 2.0
    pool_probe_timeout_s: float = 10.0
    pool_poll_interval_s: float = 0.01
    pool_straggler_window: int = 8
    pool_straggler_events: int = 3
    # --- serving: async front door (DESIGN.md §serving-frontdoor) ----------------
    # HTTP/SSE server defaults (launch/server.py overrides per flag). The
    # drain timeout is the SIGTERM hard-kill ceiling: in-flight requests get
    # this long to finish or deadline-out before the server cancels them.
    server_host: str = "127.0.0.1"
    server_port: int = 8080
    server_drain_timeout_s: float = 30.0
    server_poll_s: float = 0.001  # driver-thread idle poll between ticks
    # --- serving: open-loop traffic benchmark (benchmarks/bench_serving.py) ------
    # Poisson arrival-rate sweep (requests/s) and per-rate request count for
    # the latency-under-load report; --smoke shrinks both.
    bench_arrival_rates: tuple = (2.0, 6.0, 18.0)
    bench_requests_per_rate: int = 24
    # --- numerics ----------------------------------------------------------------
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    sub_quadratic: bool = False  # True for ssm/hybrid: long_500k runnable

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to 256 for TP divisibility + MXU alignment."""
        return ((self.vocab_size + 255) // 256) * 256

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    def param_count_estimate(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d = self.d_model
        total = self.padded_vocab * d * 2  # embed + head (untied)
        for i in range(self.n_layers):
            total += _layer_params(self, i)
        return total

    def active_param_count_estimate(self) -> int:
        d = self.d_model
        total = self.padded_vocab * d * 2
        for i in range(self.n_layers):
            total += _layer_params(self, i, active_only=True)
        return total


def _layer_params(cfg: ModelConfig, i: int, *, active_only: bool = False) -> int:
    d = cfg.d_model
    n = 0
    is_attn = True
    if cfg.family == "hybrid":
        is_attn = (i % cfg.attn_layer_period) == cfg.attn_layer_offset
    if cfg.family == "ssm":
        is_attn = False
    # attention / mixer
    if cfg.family == "ssm":
        n += 4 * d * d + d * d  # r/k/v/g/o
        n += d * cfg.d_ff * 2 + d * d  # channel mix
        return n
    if is_attn:
        if cfg.kv_lora_rank:
            qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
            n += d * cfg.n_heads * qk
            n += d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            n += cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            n += cfg.n_heads * cfg.v_head_dim * d
        else:
            hd = cfg.head_dim
            n += d * cfg.n_heads * hd  # q
            n += 2 * d * cfg.n_kv_heads * hd  # kv
            n += cfg.n_heads * hd * d  # o
    else:
        di = cfg.mamba_expand * d
        n += d * 2 * di + di * d + di * (max(d // 16, 8) + 2 * cfg.mamba_d_state)
    # ffn
    moe_layer = (
        cfg.n_experts > 0
        and i >= cfg.first_dense_layers
        and (i % cfg.moe_every) == (cfg.moe_every - 1 if cfg.moe_every > 1 else 0)
    )
    if moe_layer:
        e = cfg.experts_per_tok if active_only else cfg.n_experts
        n += e * 3 * d * cfg.d_ff
        if cfg.n_shared_experts:
            n += 3 * d * (cfg.shared_expert_ff or cfg.d_ff) * cfg.n_shared_experts
        if cfg.dense_residual:
            n += 3 * d * (cfg.dense_ff or cfg.d_ff)
    else:
        ff = cfg.dense_ff if (cfg.n_experts and cfg.dense_ff) else cfg.d_ff
        if cfg.family != "ssm":
            n += 3 * d * ff
    return n


def resolve_slo(cfg: ModelConfig, name: str) -> tuple[int, float | None, float]:
    """Map an SLO class name onto the lifecycle fields: ``(priority,
    deadline_s | None, chunk_budget_weight)``. A 0.0 class deadline resolves
    to ``None`` (the engine then applies ``cfg.request_ttl_s``). Unknown
    class names raise — a typo'd class must be an admission-time 400, not a
    silent best_effort demotion."""
    for cls, prio, deadline, weight in cfg.slo_classes:
        if cls == name:
            return int(prio), (float(deadline) if deadline else None), float(weight)
    raise KeyError(f"unknown SLO class {name!r}; "
                   f"have {[c[0] for c in cfg.slo_classes]}")


# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    fsdp_pod: bool = False  # extend FSDP over the pod axis (100B+ models)
    seq_shard: bool = False  # SP over the model axis for long sequences
    remat: str = "full"  # none | full | dots
    microbatches: int = 1
    scan_layers: bool = True
    opt_state_dtype: str = "float32"  # bfloat16 for the largest models
    param_dtype: str = "float32"
    moe_group_size: int = 1024


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    mode: str  # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}
_SMOKE: dict[str, Callable[[], ModelConfig]] = {}
_PARALLEL: dict[str, Callable[[str], ParallelConfig]] = {}


def register(name: str, full: Callable[[], ModelConfig], smoke: Callable[[], ModelConfig],
             parallel: Callable[[str], ParallelConfig] | None = None):
    _REGISTRY[name] = full
    _SMOKE[name] = smoke
    if parallel:
        _PARALLEL[name] = parallel


def get_config(name: str, *, smoke: bool = False) -> ModelConfig:
    _ensure_loaded()
    table = _SMOKE if smoke else _REGISTRY
    if name not in table:
        raise KeyError(f"unknown arch {name!r}; have {sorted(table)}")
    return table[name]()


def get_parallel_config(name: str, shape: str) -> ParallelConfig:
    _ensure_loaded()
    if name in _PARALLEL:
        return _PARALLEL[name](shape)
    return default_parallel(get_config(name), SHAPES[shape])


def default_parallel(cfg: ModelConfig, shape: ShapeConfig) -> ParallelConfig:
    big = cfg.param_count_estimate() > 30e9
    return ParallelConfig(
        fsdp_pod=big,
        # SP: long sequences always; training also for wide residual streams
        # (saved layer inputs scale with d_model — llama3-405B needs seq
        # sharded even at 4k).
        seq_shard=(shape.seq_len >= 32768 and shape.mode != "decode")
        or (shape.mode == "train" and cfg.d_model >= 6144),
        remat="full" if shape.mode == "train" else "none",
        microbatches=_default_microbatches(cfg, shape),
        opt_state_dtype="bfloat16" if big else "float32",
    )


def _default_microbatches(cfg: ModelConfig, shape: ShapeConfig) -> int:
    if shape.mode != "train":
        return 1
    # Per-device tokens ride the data axis (16-way); aim ≲ 8k tokens/device
    # per microbatch at d_model 4k, shrinking for wider models. mb must keep
    # the per-microbatch global batch divisible by the largest DP degree (32,
    # the 2-pod mesh) so both meshes shard cleanly.
    tokens = shape.seq_len * shape.global_batch
    per_dev = tokens / 16
    width_scale = max(cfg.d_model / 4096.0, 1.0)
    target = max(int(8192 / width_scale), 1024)
    mb = max(int(per_dev / target), 1)
    mb_cap = max(shape.global_batch // 32, 1)
    mb = min(mb, mb_cap)
    while mb_cap % mb:
        mb -= 1
    return max(mb, 1)


def list_archs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded():
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    from . import (  # noqa: F401
        arctic_480b,
        deepseek_v2_lite_16b,
        gemma2_27b,
        granite_8b,
        internlm2_20b,
        internvl2_26b,
        jamba_v0_1_52b,
        llama3_405b,
        musicgen_medium,
        rwkv6_3b,
        tellme_0p7b,
    )
