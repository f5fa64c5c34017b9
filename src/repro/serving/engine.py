"""Serving engine: packed-ternary chunked prefill + decode with continuous batching.

Implements the paper's end-to-end inference flow (Fig. 1): prefill the prompt
through the fused attention path, then autoregressive decode through the
decoupled matrix-vector path, weights living 2-bit-packed end to end.

``prefill_step`` / ``serve_step`` are the jit'd entry points the dry-run
lowers for the ``prefill_*`` / ``decode_*`` / ``long_*`` shapes.

**Host-sync-free decode** (DESIGN.md §decode): the token loop never round-trips
to the host per token. ``generate`` runs the whole decode as one
``jax.lax.scan`` over steps — sampling, EOS/done masking, and position
advance all on device — and materializes tokens once at the end.

**Chunked cache-resident prefill** (DESIGN.md §prefill): ``ServingEngine``
never materializes a per-request cache. Prompts are split into fixed-size
chunks drawn from ``cfg.prefill_chunk_sizes`` (default {64, 128, 256} — so
the engine compiles at most three prefill shapes, ever), and every scheduler
tick runs ONE fused jit that appends up to ``prefill_chunk_budget``
chunk-tokens straight into the batched KV cache at each slot's offset *and*
advances one decode token for every decoding slot — the batched analogue of
the paper's single-stream prefill→decode handoff, with no decode stall while
a long prompt prefills. Per-slot decode state (current token, position, done
flag, counters) stays on device; each tick issues exactly one host transfer
(``jax.device_get`` of one packed int32 array — [4, slots] on fused ticks,
[6, slots] on decode-only ticks).

Families without a chunkable attention mixer (mla / mamba / rwkv) fall back
to the legacy per-request prefill through ``prefill_bucketed``, which caches
the compiled step per length key — bucketed to the chunk grid for the dense
family, exact-length for recurrent-state/MoE families where pad tokens would
integrate into the state — so repeat lengths never retrace.

**Speculative decoding** (DESIGN.md §speculative): with
``ServingEngine(speculative=True)`` every decoding slot drafts
``spec_gamma`` candidate tokens per tick (model-free prompt-lookup over a
device-resident token history, ``serving/speculative.py``) and verifies them
in ONE chunked forward through ``Tr.verify_chunk_step`` — the ``γ+1`` chunk
appends at the slot's frontier exactly like a prefill chunk, logits come
back at every row, and the longest accepted prefix plus one model
correction retires per tick (up to ``γ+1`` tokens per weight/cache stream;
greedy output bit-identical to plain decode). Rejected rows are rolled back
by *rewinding the frontier pointer*: stale rows past it are never read
(clamped frontier masks) and the next tick's chunk overwrites them — O(1),
int8 scale side arrays included. Mixed ticks verify decoding slots AND
append prompt chunks for prefilling slots under the same
``prefill_chunk_budget``; the one-``device_get``-per-tick contract holds
(the packed array grows to ``[γ+4, slots]``). Dense-family chunked engines
only — recurrent state cannot rewind a pointer and MoE routing couples
tokens across slots — others silently stay on plain decode.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import ternary
from ..models import layers as Lyr
from ..models import transformer as Tr
from ..runtime import fault_tolerance as FT
from . import resilience as R
from . import speculative as Sp
from . import tracing
from .paging import PagedKV, PagePoolExhausted


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _engine_params(params, cfg, mode: str):
    """Offline_preprocess hook for the TL matmul engine: with
    ``cfg.matmul_engine="tl"`` the packed param tree is augmented once with
    precomputed group indices (``bitlinear.with_tl_tree``) so no jitted step
    ever unpacks/encodes weights. ``"auto"`` trees the caller prepared with
    ``with_tl_tree`` pass through idempotently; plain trees are untouched
    (the measured dispatch then resolves packed — zero behavior change)."""
    if mode == "packed" and getattr(cfg, "matmul_engine", "auto") == "tl":
        from ..core import bitlinear

        return bitlinear.with_tl_tree(params)
    return params


# ---------------------------------------------------------------------------
# Pure step functions (jit / dry-run entry points)
# ---------------------------------------------------------------------------


def make_prefill_step(cfg, *, mode: str = "packed", fused: bool | None = None):
    """prefill_step(params, batch) -> (last_logits [B, V], caches)."""

    def prefill_step(params, batch):
        logits, _, caches = Tr.forward(params, batch, cfg, None, mode=mode,
                                       collect_cache=True, fused=fused)
        return logits[:, -1], caches

    return prefill_step


def make_serve_step(cfg, *, mode: str = "packed", attn_impl: str = "auto",
                    fused: bool | None = None):
    """serve_step(params, batch, caches, pos) -> (logits [B, V], new caches).

    One new token against a KV cache of ``seq_len`` — the decode_* shapes.
    ``attn_impl`` routes cache attention to the fused Pallas decode kernel
    ("kernel"), the dense XLA form ("xla"), or backend-default ("auto");
    ``fused`` routes the linear path through the int8-resident NQD pipeline
    (default: on when ``mode="packed"``).
    """

    def serve_step(params, batch, caches, pos, page_table=None):
        return Tr.decode_step(params, batch, caches, pos, cfg, mode=mode,
                              attn_impl=attn_impl, fused=fused,
                              page_table=page_table)

    return serve_step


def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16, *,
                kv_pages: int | None = None):
    """Zeroed cache tree. ``kv_pages`` switches attention leaves to the
    page-pool layout (DESIGN.md §paged-kv) — an explicit opt-in, never
    inferred from ``cfg.kv_layout``, so ``generate``/``forward`` callers
    always build the contiguous layout."""
    shapes, _ = Tr.cache_specs(cfg, batch, max_len, dtype, kv_pages=kv_pages)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def cache_nbytes(caches) -> int:
    """Total bytes resident in a cache tree (int8 data + scale side arrays
    included) — what the serving CLIs report as the kv_cache_dtype saving.
    Accepts concrete arrays *or* the abstract ``cache_specs`` shapes tree, so
    a reference layout can be costed without allocating it."""
    import math

    return sum(math.prod(x.shape) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(caches))


def cache_savings(eng: "ServingEngine") -> tuple[int, int]:
    """(resident_bytes, bf16_layout_bytes) for an engine's caches — the
    kv_cache_dtype saving the serving CLIs print. The bf16 reference layout
    comes from abstract ``cache_specs`` at the same geometry, never
    allocated."""
    ref = Tr.cache_specs(
        dataclasses.replace(eng.cfg, kv_cache_dtype="bf16"),
        eng.slots, eng.cache_len, eng.cfg.dtype)[0]
    return cache_nbytes(eng.caches), cache_nbytes(ref)


def _resize_caches(caches, cfg, max_len: int, *, crop: bool):
    """Pad (and, with ``crop``, slice) caches to ``max_len`` on the seq axis.

    Which leaves carry a sequence axis — and which axis it is — is decided by
    *path* against the ``cache_specs`` axes tree (the leaves whose logical
    axes contain ``act_kv_seq``: attention ``k``/``v`` and their int8-cache
    ``k_scale``/``v_scale`` side arrays, MLA ``c_kv``/``k_rope``), not by
    leaf name, so nested state dicts whose leaves happen to share those names
    (or caches with no seq axis at all: mamba conv/ssm, rwkv wkv) are never
    touched. A cache whose layout disagrees with ``cfg.kv_cache_dtype``
    (scale leaves present/absent where the spec says otherwise) is rejected
    rather than silently mis-resized.
    """
    _, axes_tree = Tr.cache_specs(cfg, 1, 1)

    def rec(c, a):
        if isinstance(c, dict):
            if set(c) != set(a):
                raise ValueError(
                    f"cache layout mismatch: cache has keys {sorted(c)} but "
                    f"cfg (kv_cache_dtype={cfg.kv_cache_dtype!r}) expects "
                    f"{sorted(a)} — was this cache built under a different "
                    f"kv_cache_dtype?")
            return {k: rec(c[k], a[k]) for k in c}
        if "act_kv_seq" not in a:
            return c
        ax = a.index("act_kv_seq")
        n = c.shape[ax]
        if n > max_len and crop:
            return jax.lax.slice_in_dim(c, 0, max_len, axis=ax)
        if n < max_len:
            pads = [(0, 0)] * c.ndim
            pads[ax] = (0, max_len - n)
            return jnp.pad(c, pads)
        return c

    return rec(caches, axes_tree)


def grow_caches(caches, cfg, max_len: int):
    """Pad prefill caches (length S) out to ``max_len`` along the seq axis.
    Already-sized (or longer) caches pass through unchanged, making the call
    idempotent. Axis selection is path-based — see ``_resize_caches``."""
    return _resize_caches(caches, cfg, max_len, crop=False)


def fit_caches(caches, cfg, max_len: int):
    """Grow *or crop* caches to exactly ``max_len`` on the seq axis.

    Bucketed prefill returns caches at the bucket length, which may overshoot
    the serving cache (a 30-token prompt in a 64 bucket against a 32-token
    cache); cropped positions sit past every live frontier — only padding K/V
    ever lives there — so cropping never drops attended state.
    """
    return _resize_caches(caches, cfg, max_len, crop=True)


# ---------------------------------------------------------------------------
# Chunk schedule + length-bucketed prefill (3 compiled shapes, ever)
# ---------------------------------------------------------------------------


def chunk_schedule(length: int, sizes=(64, 128, 256)) -> list[int]:
    """Split a prompt into chunk sizes from ``sizes``, greedily large→small,
    the tail padded up to the smallest size.

    Invariant (relied on by the kernel's aliased cache-append window): each
    size divides every larger size, so when a chunk of size ``C`` is issued
    the running offset — a sum of chunks all ≥ C — is a multiple of C.
    """
    sizes = sorted(sizes)
    for a, b in zip(sizes, sizes[1:]):
        if b % a:
            raise ValueError(f"chunk sizes must form a divisibility chain: {sizes}")
    rem = _round_up(max(length, 1), sizes[0])
    out = []
    while rem:
        c = next(s for s in reversed(sizes) if s <= rem)
        out.append(c)
        rem -= c
    return out


def bucket_length(s: int, sizes=(64, 128, 256)) -> int:
    """Bucket a prompt length to the chunk grid: the smallest size that fits,
    else the next multiple of the largest size."""
    sizes = sorted(sizes)
    for b in sizes:
        if s <= b:
            return b
    return _round_up(s, sizes[-1])


# Compiled bucketed-prefill cache: keyed by (cfg, mode, bucket). Configs are
# frozen dataclasses (hashable), so distinct prompt lengths that share a
# bucket reuse one compiled step instead of recompiling per length.
_BUCKETED_PREFILL_CACHE: dict = {}


def prefill_bucketed(params, cfg, prompts: jax.Array, *, mode: str = "packed",
                     lengths: jax.Array | None = None,
                     fused: bool | None = None):
    """Length-bucketed prefill: pads ``prompts [B, S]`` up to the chunk-size
    grid (attention-masked padding — pad tokens sit past every row's causal
    frontier, and the returned logits are gathered at each row's true last
    token), so prefill compiles once per *bucket*, not per prompt length.

    Bucketing is only sound when pad tokens cannot reach real state: the
    ``dense`` family's attention K/V caches index by position, so pad rows
    land past every live frontier. Recurrent state (rwkv wkv / mamba conv-ssm)
    *integrates* the pads, and MoE capacity routing lets them crowd out real
    tokens — those families keep exact-length prefill, still cached per
    (cfg, mode, length) so repeat lengths don't retrace.

    Returns (last_logits [B, V], caches with seq length = bucket | S).
    """
    b, s = prompts.shape
    if cfg.family == "dense":
        sizes = tuple(cfg.prefill_chunk_sizes) or (64, 128, 256)
        bucket = bucket_length(s, sizes)
    else:
        bucket = s  # pad-unsafe families: exact length, cached per length
    key_t = (cfg, mode, bucket, fused)
    fn = _BUCKETED_PREFILL_CACHE.get(key_t)
    if fn is None:
        def step(params, batch, lens):
            logits, _, caches = Tr.forward(params, batch, cfg, None, mode=mode,
                                           collect_cache=True, fused=fused)
            last = jnp.take_along_axis(
                logits, (lens - 1)[:, None, None], axis=1
            )[:, 0]
            return last, caches

        fn = jax.jit(step)
        _BUCKETED_PREFILL_CACHE[key_t] = fn
    padded = jnp.pad(prompts, ((0, 0), (0, bucket - s)))
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    return fn(params, {"tokens": padded}, jnp.asarray(lengths, jnp.int32))


# ---------------------------------------------------------------------------
# Batched generation loop (greedy / temperature sampling)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GenerationResult:
    tokens: Any  # [B, T] generated ids
    prefill_logits: Any


def _sample(logits, key, temperature, *, greedy: bool):
    """Greedy argmax or temperature sampling; one definition for the prefill
    token and every scan step. ``greedy`` is static; ``temperature`` may be a
    traced scalar so distinct temperatures share one compiled scan."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


# Jitted decode-scan cache: configs are frozen dataclasses (hashable), so the
# static context keys the compiled loop — repeat generate() calls with the
# same shape/config reuse the compiled scan instead of retracing it.
# Temperature is a *traced* operand (only greedy-vs-stochastic is static), so
# per-request temperatures don't grow the cache or retrace.
_DECODE_SCAN_CACHE: dict = {}


def _decode_scan(cfg, *, steps: int, mode: str, greedy: bool,
                 eos_id: int | None, attn_impl: str, fused: bool | None):
    key_t = (cfg, steps, mode, greedy, eos_id, attn_impl, fused)
    fn = _DECODE_SCAN_CACHE.get(key_t)
    if fn is not None:
        return fn

    def run(params, caches, tok0, pos0, done0, key, temperature):
        def body(carry, _):
            tok, pos, done, caches, k = carry
            logits, caches = Tr.decode_step(params, {"tokens": tok[:, None]}, caches,
                                            pos, cfg, mode=mode, attn_impl=attn_impl,
                                            fused=fused)
            k, sub = jax.random.split(k)
            nxt = _sample(logits, sub, temperature, greedy=greedy)
            if eos_id is not None:
                nxt = jnp.where(done, jnp.int32(eos_id), nxt)
                new_done = done | (nxt == eos_id)
            else:
                new_done = done
            pos = pos + jnp.where(done, 0, 1).astype(jnp.int32)
            return (nxt, pos, new_done, caches, k), nxt

        _, toks = jax.lax.scan(body, (tok0, pos0, done0, caches, key), None,
                               length=steps - 1)
        return jnp.concatenate([tok0[:, None], toks.T], axis=1)

    fn = jax.jit(run)
    _DECODE_SCAN_CACHE[key_t] = fn
    return fn


def generate(
    params,
    cfg,
    prompts: jax.Array,  # [B, S] token ids (right-aligned, no padding support here)
    *,
    steps: int,
    mode: str = "eval",
    temperature: float = 0.0,
    key: jax.Array | None = None,
    eos_id: int | None = None,
    attn_impl: str = "auto",
    fused: bool | None = None,
) -> GenerationResult:
    """Device-resident generation: bucketed prefill, then one ``lax.scan``.

    Prefill goes through ``prefill_bucketed`` — distinct prompt lengths that
    share a bucket on the ``cfg.prefill_chunk_sizes`` grid reuse one compiled
    step. The scan body runs decode_step + sampling + per-slot done masking
    fully on device; no token ever crosses to the host until the final
    result. With ``eos_id`` set, finished slots emit ``eos_id`` and stop
    advancing their cache position (their decode still runs — a fixed-shape
    batch — but its writes land on a frozen position, which
    ``update_kv_cache`` overwrites idempotently). Greedy output is
    bit-identical to the per-token Python loop this replaces.
    """
    b, s = prompts.shape
    params = _engine_params(params, cfg, mode)
    last_logits, caches = prefill_bucketed(params, cfg, prompts, mode=mode,
                                           fused=fused)
    caches = fit_caches(caches, cfg, s + steps)

    key = key if key is not None else jax.random.PRNGKey(0)
    greedy = temperature <= 0
    tok0 = _sample(last_logits, key, temperature, greedy=greedy)
    pos0 = jnp.full((b,), s, jnp.int32)
    done0 = (tok0 == eos_id) if eos_id is not None else jnp.zeros((b,), bool)

    if steps > 1:
        scan = _decode_scan(cfg, steps=steps, mode=mode, greedy=greedy,
                            eos_id=eos_id, attn_impl=attn_impl, fused=fused)
        tokens = scan(params, caches, tok0, pos0, done0, key, jnp.float32(temperature))
    else:
        tokens = tok0[:, None]
    return GenerationResult(tokens=tokens, prefill_logits=last_logits)


# ---------------------------------------------------------------------------
# Continuous batching scheduler (slot-based, chunked prefill)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    rid: int
    prompt: Any  # np/jnp [S]
    max_new: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # --- lifecycle (DESIGN.md §resilience) ---------------------------------
    # Every request ends in exactly one terminal status (resilience.Status);
    # `done` stays the legacy "terminal" bool for existing callers.
    status: R.Status = R.Status.PENDING
    status_detail: str | None = None
    priority: int = 0  # preemption: higher wins a slot from a lower
    deadline_s: float | None = None  # TTL from submit (None: cfg.request_ttl_s)
    submitted_at: float | None = None
    finished_at: float | None = None
    cancel_requested: bool = False
    preemptions: int = 0  # times evicted + requeued for re-prefill
    migrations: int = 0  # times moved to another replica after a crash/hang
    # SLO class (DESIGN.md §replica-pool): the class name this request was
    # admitted under (None outside the pool) and its chunk-budget weight —
    # the highest weight among slots mid-prefill scales the engine's
    # per-tick prefill_chunk_budget (1.0, the default, is bit-identical to
    # the pre-pool engine).
    slo: str | None = None
    budget_weight: float = 1.0
    _seq: int = 0  # submission order (preemption tie-breaks, FIFO in priority)
    # speculative-decoding stats (0 unless served by a speculative engine):
    # drafts offered / drafts accepted across this request's verify ticks.
    spec_drafted: int = 0
    spec_accepted: int = 0
    # trace stamps (perf_counter ns, DESIGN.md §tracing): front-door arrival,
    # start of the current queue wait, the latest admission, and the read-back
    # of the first token
    t_arrival_ns: int | None = None
    t_queued_ns: int | None = None
    t_admitted_ns: int | None = None
    t_first_ns: int | None = None

    @property
    def spec_acceptance(self) -> float:
        """Fraction of drafted tokens accepted (0.0 when never drafted)."""
        return self.spec_accepted / self.spec_drafted if self.spec_drafted else 0.0

    def expired(self, now: float) -> bool:
        return (self.deadline_s is not None and self.submitted_at is not None
                and now - self.submitted_at > self.deadline_s)


def snapshot_request(req: Request) -> Request:
    """Resumable clone of one request: prompt + emitted history (copies —
    the donor's arrays/lists are never aliased) + the RNG-free lifecycle
    fields. Re-prefilling prompt+history with the remaining budget
    reproduces the greedy stream bit-identically (the §resilience
    preempt-resume invariant), so this is the unit of cross-replica
    migration (DESIGN.md §replica-pool)."""
    snap = Request(rid=req.rid, prompt=np.array(req.prompt),
                   max_new=req.max_new, generated=list(req.generated))
    snap.priority = req.priority
    snap.deadline_s = req.deadline_s
    snap.submitted_at = req.submitted_at
    snap.preemptions = req.preemptions
    snap.migrations = req.migrations
    snap.slo = req.slo
    snap.budget_weight = req.budget_weight
    snap.t_arrival_ns = req.t_arrival_ns
    snap.t_admitted_ns = req.t_admitted_ns
    snap.t_first_ns = req.t_first_ns
    return snap


@dataclasses.dataclass
class _PrefillPlan:
    """Host-side chunk bookkeeping for a slot mid-prefill."""
    tokens: np.ndarray  # [P] prompt padded to the chunk schedule
    chunks: list  # chunk sizes, greedy large→small
    ci: int  # next chunk index
    off: int  # cache offset consumed so far (≡ 0 mod chunks[ci])
    true_len: int  # unpadded prompt length


class ServingEngine:
    """Continuous batching over a fused chunked-prefill + decode tick.

    Fixed B decode slots; finished requests retire their slot, queued
    requests are admitted into free slots and prefill *incrementally*: each
    tick appends at most ``cfg.prefill_chunk_budget`` chunk-tokens into the
    batched KV cache (at each slot's frontier, via the ``prefill_append``
    path) while every decoding slot still advances one token — prefill never
    stalls decode, and per-request caches are never materialized or
    host-scattered. Chunk sizes come from ``cfg.prefill_chunk_sizes``, so at
    most ``len(sizes)`` fused prefill shapes are ever compiled (3 by
    default); ticks with no prefill work reuse the plain decode step.

    The cache carries ``chunk_max`` trash rows past ``max_len``: slots with
    no work this tick are diverted there (chunk writes at ``trash_base``,
    decode writes at the last row), keeping every tick a fixed-shape batched
    call without masking machinery inside the kernels.

    All per-slot decode state (current token, position, done flag, generated
    count, budget) lives on device; ``step()`` issues exactly one host
    transfer per scheduler tick — ``jax.device_get`` of one packed int32
    array ([4, slots] fused tick, [6, slots] decode-only tick, one extra
    guard-flag row with ``guards`` on) — regardless of slot count or tokens
    generated.

    **Resilience** (DESIGN.md §resilience): every request ends in exactly one
    terminal ``resilience.Status``; ``submit`` applies bounded-queue
    backpressure (``queue_cap`` / ``cfg.admission_queue_cap``), requests
    carry deadlines/TTL (``cfg.request_ttl_s``) and can be ``cancel()``ed
    host-side; under cache pressure a strictly-higher-priority waiter
    preempts the lowest-priority slot (frontier rewind + requeue for
    re-prefill from prompt + emitted history). ``guards`` (default on) rides
    in-tick finite/overflow checks on logits and freshly written quant
    scales in the packed transfer; a flagged slot is quarantined without
    touching co-batched slots. A raising tick flips a sticky kernel→XLA
    ``attn_impl`` fallback; collapsed speculative acceptance auto-disables
    verify ticks; ``step()`` never raises. A ``fault_plan``
    (``resilience.FaultPlan``) drives deterministic chaos injection for
    tests/benchmarks — with no plan the tick jits carry no injection
    operands at all.
    """

    def __init__(self, params, cfg, *, slots: int = 8, max_len: int = 2048,
                 mode: str = "eval", eos_id: int = -1, attn_impl: str = "auto",
                 prefill: str = "auto", fused: bool | None = None,
                 speculative: bool = False, spec_gamma: int | None = None,
                 queue_cap: int | None = None,
                 fault_plan: R.FaultPlan | None = None, guards: bool = True,
                 clock=time.monotonic,
                 straggler: FT.StragglerMonitor | None = None,
                 replica_id: int | str | None = None,
                 device: jax.Device | None = None):
        # ``device`` pins the params, the caches and every tick to one device
        # (the replica pool puts each replica on its own chip); None leaves
        # placement to JAX's default device.
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
        self.params = _engine_params(params, cfg, mode)
        self.cfg, self.mode = cfg, mode
        self.fused = fused  # int8-resident NQD pipeline (None: on iff packed)
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.attn_impl = attn_impl
        if prefill == "auto":
            # chunked needs per-token batch independence: attention + dense
            # FFN only (MoE capacity dropping couples tokens across slots, so
            # trash-diverted rows could perturb live routing — opt in
            # explicitly with prefill="chunked" if capacity is generous).
            prefill = "chunked" if cfg.family == "dense" else "legacy"
        self.prefill = prefill
        sizes = tuple(sorted(cfg.prefill_chunk_sizes)) or (64, 128, 256)
        # Drop chunk sizes no admissible prompt (len < max_len) can ever
        # fill — otherwise a 64-row engine pays a 256-row trash tail (8x KV
        # memory) for chunk shapes that would never compile anyway.
        self.chunk_sizes = tuple(
            s for s in sizes if s <= bucket_length(max_len, sizes))
        chunk_schedule(1, self.chunk_sizes)  # validate the divisibility chain
        cmax = self.chunk_sizes[-1]
        if self.prefill == "chunked":
            # usable [0, trash_base) + one chunk_max trash tail for diverted
            # writes; trash_base is a multiple of every chunk size.
            self.trash_base = _round_up(max_len, cmax)
            self.cache_len = self.trash_base + cmax
        else:
            self.trash_base = None
            self.cache_len = max_len
        # -- paged KV layout (DESIGN.md §paged-kv) ----------------------------
        # cfg.kv_layout="paged" swaps the per-slot contiguous cache rows for
        # a page pool + per-slot page table: a host allocator with refcounts
        # backs copy-on-write prefix sharing, and every unmapped table entry
        # points at ONE permanently-allocated garbage page (so trash-diverted
        # and idle writes land on dead rows without any masking). Chunked
        # engines only — the legacy path scatters whole per-request caches.
        if getattr(cfg, "kv_layout", "contiguous") == "paged":
            if self.prefill != "chunked":
                raise ValueError(
                    "kv_layout='paged' requires the chunked prefill path "
                    f"(family={cfg.family!r} resolved prefill={self.prefill!r})")
            ps = int(cfg.kv_page_size)
            if ps <= 0 or self.chunk_sizes[0] % ps:
                raise ValueError(
                    f"kv_page_size={ps} must divide the smallest prefill "
                    f"chunk size ({self.chunk_sizes[0]}) so every chunk "
                    f"append covers whole pages")
            self.paged = PagedKV(slots=slots, cache_len=self.cache_len,
                                 page_size=ps,
                                 num_pages=int(cfg.kv_num_pages),
                                 prefix_cache=bool(cfg.prefix_cache))
            # host mirror of the device frontier for dec_active slots — the
            # page allocator needs this tick's written blocks *before* the
            # device round-trip (updated from the same packed state the
            # scheduler already reads, so no extra transfer).
            self._pos_host = np.zeros((slots,), np.int32)
        else:
            self.paged = None
            self._pos_host = None
        self.live = [None] * slots  # slot -> Request
        self.queue: list[Request] = []
        self._plan: list[_PrefillPlan | None] = [None] * slots
        self._pending_first: set[int] = set()  # legacy path: unrecorded prefill token
        self._fused: dict[int, Any] = {}  # chunk size -> fused tick jit
        self._serve = _serve_step_cached(cfg, mode, attn_impl, fused)
        # Speculative decode (DESIGN.md §speculative): chunked dense-family
        # engines only — recurrent state cannot rewind a frontier pointer and
        # MoE capacity routing couples tokens across slots, so those families
        # silently stay on plain decode.
        self.speculative = bool(speculative) and self.prefill == "chunked"
        self.spec_gamma = int(spec_gamma if spec_gamma is not None
                              else cfg.spec_gamma)
        if self.speculative and not (1 <= self.spec_gamma < cmax):
            raise ValueError(
                f"spec_gamma={self.spec_gamma} must be in [1, {cmax}): the "
                f"γ+1 verify chunk must fit the chunk_max trash tail")
        # Device-resident token history per slot (prompt + emissions) — the
        # prompt-lookup drafter's corpus; positions <= pos are live.
        self.hist = None
        self._init_device_state(hist=self.speculative)
        self._spec: dict[int | None, Any] = {}  # chunk (or None) -> spec tick jit
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0
        # -- resilience layer (DESIGN.md §resilience) -------------------------
        self.queue_cap = (int(cfg.admission_queue_cap) if queue_cap is None
                          else int(queue_cap))  # 0 = unbounded
        self.guards = bool(guards)  # numerics quarantine flag row in packed
        self._clock = clock
        self.straggler = straggler or FT.StragglerMonitor()
        self.tick_count = 0
        # Pool-facing identity + health counters (DESIGN.md §replica-pool):
        # replica_id names this engine in aggregated stats (operators can
        # tell WHICH replica quarantined a request); uptime/tick counters
        # are monotonic for the engine object's lifetime — device re-init
        # (_fail_all_live) does not reset them. consecutive_tick_failures
        # counts ticks that entered the exception path (even if the sticky
        # XLA fallback recovered them) and resets on the next clean tick —
        # the pool's drain gate.
        self.replica_id = replica_id
        self._started_at = self._clock()
        self.consecutive_tick_failures = 0
        # Tick-stamped resilience/serving event ring: bounded so a days-long
        # server cannot leak host memory through its own bookkeeping. When
        # full, the oldest event is dropped and counted (stats() reports it).
        self.events: list[dict] = []  # (kind, tick, ...) resilience events
        self.events_cap = int(getattr(cfg, "stats_ring_events", 4096))
        self.events_dropped = 0
        # serving-path spans (DESIGN.md §tracing) go to one process-wide
        # ring, sized by the process's owner; a tick's serve.tick attributes
        # and the read-back time of its one transfer are kept for the tick's
        # span and request spans
        self._tick_attrs: dict = {}
        self._t_read_ns = 0
        self._admitted = 0
        # Incremental delivery hooks (DESIGN.md §serving-frontdoor): after
        # every step(), on_emit(req, new_tokens) fires once per request that
        # emitted this tick and on_finish(req) once per request that reached
        # a terminal status inside the tick — both on the caller's (driver)
        # thread, after the tick's device transfer, never mid-dispatch. The
        # async server bridges them onto per-stream queues; None (default)
        # keeps the tick path hook-free.
        self.on_emit = None  # callable(req, list[int]) | None
        self.on_finish = None  # callable(req) | None
        self.status_counts: collections.Counter = collections.Counter()
        self.xla_fallback = False  # sticky kernel→XLA impl fallback tripped
        self._seq = 0  # submission counter (priority FIFO / preemption ties)
        self._fault_plan = fault_plan
        # static flag: with no plan the tick jits compile WITHOUT any
        # injection operand — production graphs are byte-identical to a
        # fault-capable engine that never fires (where(False, ...) no-ops)
        self._debug_faults = fault_plan is not None
        self._advance = _advance_cached(cfg, eos_id, max_len, self.guards,
                                        self._debug_faults,
                                        paged=self.paged is not None)

    # -- lifecycle ----------------------------------------------------------

    def _on_device(self):
        """Pin the arrays created inside the block to this engine's device
        (a no-op without one)."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    @property
    def attn_path(self) -> str:
        """The attention form the ticks dispatch: ``"auto"`` resolves to the
        Pallas kernels on a TPU and to the XLA form elsewhere."""
        if self.attn_impl != "auto":
            return self.attn_impl
        platform = (self.device.platform if self.device is not None
                    else jax.default_backend())
        return "kernel" if platform == "tpu" else "xla"

    @property
    def kv_write(self) -> str:
        """How the ticks write the KV cache (DESIGN.md §decode):
        ``"in_place"`` — rows written into the stacked cache the layer loop
        carries — where the attention runs on the kernels over a contiguous
        cache, else ``"rewrite"``."""
        return ("in_place" if Tr.kv_in_place(self.attn_path,
                                             paged=self.paged is not None)
                else "rewrite")

    def submit(self, req: Request) -> bool:
        """Enqueue ``req``; returns False (terminal ``FAILED``,
        ``status_detail="queue_full"``) when the bounded admission queue is
        full — backpressure instead of silent growth. A rejected request may
        be resubmitted later: a successful submit resets its lifecycle."""
        if self.queue_cap and len(self.queue) >= self.queue_cap:
            self._finish(None, req, R.Status.FAILED, detail="queue_full")
            self._event("admission_reject", rid=req.rid, detail="queue_full")
            return False
        req.done = False
        req.status = R.Status.QUEUED
        req.status_detail = None
        now = time.perf_counter_ns()
        if req.t_arrival_ns is None:  # no front door: arrival is the submit
            req.t_arrival_ns = now
        req.t_queued_ns = (req.t_arrival_ns if req.t_admitted_ns is None
                           else now)
        if req.submitted_at is None:
            req.submitted_at = self._clock()
        if req.deadline_s is None and self.cfg.request_ttl_s > 0:
            req.deadline_s = float(self.cfg.request_ttl_s)
        req._seq = self._seq
        self._seq += 1
        self.queue.append(req)
        return True

    def cancel(self, rid: int) -> bool:
        """Host-side cancellation: mark the request (queued or running);
        the next ``step()`` retires it with status ``CANCELLED``."""
        for req in self.queue + [r for r in self.live if r is not None]:
            if req.rid == rid:
                req.cancel_requested = True
                return True
        return False

    def _event(self, kind: str, **detail):
        if self.events_cap and len(self.events) >= self.events_cap:
            del self.events[0]  # fixed-size ring: drop oldest, keep counting
            self.events_dropped += 1
        self.events.append({"kind": kind, "tick": self.tick_count, **detail})

    def _finish(self, slot: int | None, req: Request, status: R.Status,
                detail: str | None = None):
        """The one retirement bookkeeper: stamp the terminal status and (for
        a slotted request) free the slot. Device-side state needs no
        cleanup — rows past the next occupant's writes are dead by the
        rollback invariant, and dec_active/plan masks are host-derived."""
        req.done = True
        req.status = status
        if detail is not None:
            req.status_detail = detail
        req.finished_at = self._clock()
        self.status_counts[status] += 1
        if slot is not None:
            self.live[slot] = None
            self._plan[slot] = None
            self._pending_first.discard(slot)
            if self.paged is not None:
                # deref the slot's pages (shared prefix pages survive while
                # the trie or another slot still holds them)
                self.paged.release(slot)
                self._pos_host[slot] = 0

    def _terminal_status(self, req: Request) -> R.Status:
        """Why a device-side retirement (`_retire`) fired: EOS or budget are
        normal completions (``OK``); otherwise the frontier hit the cache
        ceiling (``CACHE_EXHAUSTED``) — derivable host-side from the emitted
        stream, no extra transfer."""
        if req.generated and req.generated[-1] == self.eos_id:
            return R.Status.OK
        if len(req.generated) >= req.max_new:
            return R.Status.OK
        return R.Status.CACHE_EXHAUSTED

    def _quarantine(self, slot: int, req: Request, flag: int):
        """Numerics guard tripped on ``slot``: discard this tick's emissions
        for the slot, terminate the request, free the slot. Co-batched slots
        are untouched — their rows never read the poisoned slot's cache."""
        self._event("quarantine", rid=req.rid, slot=slot, flag=int(flag))
        self._finish(slot, req, R.Status.QUARANTINED,
                     detail=f"guard_flag={int(flag)}")

    def _expire_and_cancel(self, now: float):
        """Deadline/TTL expiry + host cancellation, queue and slots both."""
        keep = []
        for req in self.queue:
            if req.cancel_requested:
                self._finish(None, req, R.Status.CANCELLED)
            elif req.expired(now):
                self._finish(None, req, R.Status.DEADLINE_EXCEEDED)
            else:
                keep.append(req)
        if len(keep) != len(self.queue):
            self.queue = keep
        for slot in range(self.slots):
            req = self.live[slot]
            if req is None:
                continue
            if req.cancel_requested:
                self._finish(slot, req, R.Status.CANCELLED)
            elif req.expired(now):
                self._finish(slot, req, R.Status.DEADLINE_EXCEEDED)

    def _fail_all_live(self, detail: str):
        """Last-resort containment: a tick failed even on the XLA fallback
        (or invalidated its donated buffers). Every live request terminates
        ``FAILED`` (emitted tokens kept) and the device state is
        re-initialized so the engine keeps serving the queue."""
        self._event("tick_failure", detail=detail)
        for slot in range(self.slots):
            req = self.live[slot]
            if req is not None:
                self._finish(slot, req, R.Status.FAILED, detail=detail)
        if self.paged is not None:
            # fresh pool + trie: device pages may hold garbage post-failure,
            # and a poisoned interned prefix must not leak into new requests
            self.paged = PagedKV(slots=self.slots, cache_len=self.cache_len,
                                 page_size=self.paged.page_size,
                                 num_pages=self.paged.num_pages,
                                 prefix_cache=self.paged.prefix_cache)
            self._pos_host[:] = 0
        self._init_device_state(hist=self.hist is not None)

    def _init_device_state(self, *, hist: bool):
        """Fresh caches and per-slot decode state on the engine's device."""
        s = self.slots
        with self._on_device():
            state = {
                "caches": init_caches(
                    self.cfg, s, self.cache_len, dtype=self.cfg.dtype,
                    kv_pages=(self.paged.num_pages if self.paged is not None
                              else None)),
                "pos": jnp.zeros((s,), jnp.int32),
                "cur_tok": jnp.zeros((s,), jnp.int32),
                "done": jnp.zeros((s,), bool),
                "gen_count": jnp.zeros((s,), jnp.int32),
                "max_new_arr": jnp.zeros((s,), jnp.int32),
            }
            if hist:
                state["hist"] = jnp.zeros((s, self.cache_len), jnp.int32)
        if self.device is not None:
            # committed like the tick outputs that replace it, so the first
            # tick hits the same compiled program as every later one
            state = jax.device_put(state, self.device)
        for name, value in state.items():
            setattr(self, name, value)

    def stats(self) -> dict:
        """Engine-level resilience/serving stats for CLIs and tests."""
        return {
            "replica_id": self.replica_id,
            "ticks": self.tick_count,
            "uptime_s": max(self._clock() - self._started_at, 0.0),
            "consecutive_tick_failures": self.consecutive_tick_failures,
            "statuses": {s.name: n for s, n in sorted(
                self.status_counts.items(), key=lambda kv: kv[0].name)},
            "events": [dict(e) for e in self.events],
            "events_dropped": self.events_dropped,
            "queued": len(self.queue),
            "live": sum(r is not None for r in self.live),
            "straggler": self.straggler.report(),
            "attn_impl": self.attn_path,
            "xla_fallback": self.xla_fallback,
            "speculative": self.speculative,
            "spec_acceptance": self.spec_acceptance_rate,
            "preemptions": sum(1 for e in self.events
                               if e["kind"] == "preempt"),
            "quarantined": self.status_counts.get(R.Status.QUARANTINED, 0),
            "kv_layout": "paged" if self.paged is not None else "contiguous",
            "kv_write": self.kv_write,
            "paged": self.paged.stats() if self.paged is not None else None,
            "compiles": tracing.recorder().compiles_in(self.replica_id),
        }

    def export_requests(self) -> list[Request]:
        """Resumable snapshot of every non-terminal request — the crash-
        failover export (DESIGN.md §replica-pool).

        Each snapshot is a *fresh* :class:`Request` carrying exactly the
        host state a surviving replica needs to continue the stream:
        prompt, emitted history (a copy — the donor's list is never
        aliased), remaining budget (``max_new`` minus the emitted history,
        which ``_admit`` re-derives), and the RNG-free lifecycle fields
        (priority/deadline/submitted_at/SLO class). No device state crosses:
        re-prefilling prompt+history with the remaining budget reproduces
        the stream bit-identically — the §resilience preempt-resume
        invariant generalized across engine boundaries.

        Safe to call on an engine whose driver thread is dead (the normal
        crash-failover caller) and GIL-safe against a *hung* driver that
        later wakes: each request's ``generated`` only ever grows
        append-only on the driver thread, so a concurrent snapshot is a
        consistent prefix of the true stream.
        """
        return [snapshot_request(req)
                for req in list(self.queue)
                + [r for r in self.live if r is not None]
                if not req.done]

    @property
    def prefilling_slots(self) -> int:
        """Slots currently mid-prefill (chunks still pending)."""
        return sum(p is not None for p in self._plan)

    @property
    def decoding_slots(self) -> int:
        """Live slots past their prefill (decoding one token per tick)."""
        return sum(r is not None and p is None
                   for r, p in zip(self.live, self._plan))

    @property
    def compiled_prefill_shapes(self) -> int:
        """Tick shapes compiled so far: plain fused-prefill jits (≤
        len(cfg.prefill_chunk_sizes)) plus, on a speculative engine, spec
        tick jits (≤ len(sizes) mixed + 1 verify-only)."""
        return len(self._fused) + len(self._spec)

    @property
    def spec_acceptance_rate(self) -> float:
        """Aggregate drafted-token acceptance across all verify ticks."""
        return (self.spec_accepted_total / self.spec_drafted_total
                if self.spec_drafted_total else 0.0)

    # -- admission ----------------------------------------------------------

    def _admit(self, slot: int, req: Request) -> bool:
        """Admit ``req`` into ``slot``; returns False (request rejected with a
        terminal status and no further output) when the prompt cannot fit the
        cache — one oversized request must not crash the scheduler and strand
        the rest. A preempted request (``generated`` non-empty) re-prefills
        from its prompt + emitted history with the remaining budget, so its
        continuation is exactly what an uncontended run would have decoded."""
        # Deadlines are re-judged at admission time, not only at the top of
        # the tick: a slow tick (compile, straggler) can expire a queued
        # request between the tick-top expiry pass and this pop — admitting
        # it would burn a slot and prefill chunks for output nobody can use.
        # Cancellation gets the same courtesy (same race window).
        now = self._clock()
        if req.cancel_requested:
            self._finish(None, req, R.Status.CANCELLED)
            return False
        if req.expired(now):
            self._finish(None, req, R.Status.DEADLINE_EXCEEDED)
            return False
        prompt = np.asarray(req.prompt)
        remaining = req.max_new
        if req.generated:  # resume after preemption: prompt + emitted history
            prompt = np.concatenate(
                [prompt, np.asarray(req.generated, dtype=prompt.dtype)])
            remaining = req.max_new - len(req.generated)
        if prompt.shape[0] == 0 or prompt.shape[0] >= self.max_len:
            # empty/oversized prompts are admission failures; a *resumed*
            # request that no longer fits simply ran out of cache mid-flight
            status = (R.Status.CACHE_EXHAUSTED if req.generated
                      else R.Status.FAILED)
            self._finish(None, req, status,
                         detail=None if req.generated else "bad_prompt")
            return False
        if prompt.shape[0] >= self.max_len - 1 and req.generated:
            # one row of headroom is the decode loop's own ceiling predicate
            self._finish(None, req, R.Status.CACHE_EXHAUSTED)
            return False
        req.status = R.Status.RUNNING
        now = time.perf_counter_ns()
        tracing.interval("serve.queue", req.t_queued_ns or now, now,
                         rid=req.rid, replica=self.replica_id,
                         requeued=req.t_admitted_ns is not None)
        req.t_admitted_ns = now
        self._admitted += 1
        if self.prefill == "legacy":
            self._prefill_slot(slot, req, prompt, remaining)
            return True
        plen = int(prompt.shape[0])
        tail_start = 0
        if self.paged is not None:
            # radix-trie prefix reuse (DESIGN.md §paged-kv): map every
            # matched prompt page read-only (refcount++) and prefill only
            # the tail. tail_start is floored to the LARGEST chunk size so
            # every issued chunk C still satisfies C | off (the aliased
            # append-window invariant for both cache layouts); the last
            # prompt token is never skipped — its logits seed decode.
            tail_start = self.paged.admit(slot, prompt,
                                          chunk0=self.chunk_sizes[-1])
            if tail_start > 0:
                self._event("prefix_hit", rid=req.rid, slot=slot,
                            tokens=tail_start)
        chunks = chunk_schedule(plen - tail_start, self.chunk_sizes)
        padded = np.zeros((tail_start + sum(chunks),), np.int64)
        padded[:plen] = prompt
        self._plan[slot] = _PrefillPlan(tokens=padded, chunks=chunks, ci=0,
                                        off=tail_start, true_len=plen)
        self.live[slot] = req
        self.max_new_arr = self.max_new_arr.at[slot].set(remaining)
        if self.speculative:  # seed the drafter's history with the prompt
            self.hist = self.hist.at[slot, : prompt.shape[0]].set(
                jnp.asarray(prompt, jnp.int32))
        return True

    def _pop_queued(self) -> Request:
        """Highest-priority waiter, FIFO within a priority level."""
        i = max(range(len(self.queue)),
                key=lambda j: (self.queue[j].priority, -self.queue[j]._seq))
        return self.queue.pop(i)

    def _preempt(self, slot: int):
        """Evict ``slot``'s request and requeue it for re-prefill from
        prompt + emitted history. The eviction itself is free: the moment the
        host stops referencing the slot, its cache rows are past every live
        frontier — dead by the rollback invariant (DESIGN.md §speculative) —
        and the next occupant's chunk writes overwrite them."""
        req = self.live[slot]
        self._event("preempt", rid=req.rid, slot=slot,
                    priority=req.priority, emitted=len(req.generated))
        req.preemptions += 1
        req.status = R.Status.QUEUED
        req.t_queued_ns = time.perf_counter_ns()
        self.live[slot] = None
        self._plan[slot] = None
        self._pending_first.discard(slot)
        if self.paged is not None:
            self.paged.release(slot)
            self._pos_host[slot] = 0
        req._seq = self._seq  # requeued at the back of its priority level
        self._seq += 1
        self.queue.append(req)

    def _admission(self):
        """Fill free slots from the queue (highest priority first), then —
        under cache pressure (all slots occupied, waiters remain) — let a
        strictly-higher-priority waiter preempt the lowest-priority slot
        (tie: most recently submitted). ``<=`` never preempts, so a requeued
        victim cannot thrash its own replacement."""
        for slot in range(self.slots):
            while self.live[slot] is None and self.queue:
                if self._admit(slot, self._pop_queued()):
                    break  # rejected requests don't consume the slot
        rounds = 0
        while self.queue and rounds < self.slots:
            waiter = max(self.queue, key=lambda r: (r.priority, -r._seq))
            live = [s for s in range(self.slots) if self.live[s] is not None]
            if not live:
                break
            victim = min(live, key=lambda s: (self.live[s].priority,
                                              -self.live[s]._seq))
            if waiter.priority <= self.live[victim].priority:
                break
            rounds += 1
            self._preempt(victim)
            self.queue.remove(waiter)
            while not self._admit(victim, waiter) and self.queue:
                waiter = self._pop_queued()  # refill the freed slot

    def _prefill_slot(self, slot: int, req: Request,
                      prompt: np.ndarray | None = None,
                      remaining: int | None = None):
        # Legacy per-request prefill (non-attn mixer families): bucketed to
        # the chunk-size grid so compiles are per bucket, then the per-request
        # caches are scattered into the slot. The chunked path never runs
        # this — its chunks land in the batched cache directly. ``prompt`` /
        # ``remaining`` carry a preempted request's resume state (prompt +
        # emitted history, budget left) — None means a fresh admission.
        prompt = jnp.asarray(req.prompt if prompt is None else prompt)
        remaining = req.max_new if remaining is None else remaining
        logits, caches = prefill_bucketed(self.params, self.cfg, prompt[None],
                                          mode=self.mode, fused=self.fused)
        caches = fit_caches(caches, self.cfg, self.cache_len)

        # generic per-leaf scatter on the batch axis
        def rec(dst, src):
            if isinstance(dst, dict):
                return {k: rec(dst[k], src[k]) for k in dst}
            idx = [slice(None)] * dst.ndim
            # batch axis: first axis where dst == slots and src == 1
            for ax in range(dst.ndim):
                if dst.shape[ax] == self.slots and src.shape[ax] == 1:
                    idx[ax] = slice(slot, slot + 1)
                    break
            return dst.at[tuple(idx)].set(src.astype(dst.dtype))

        self.caches = rec(self.caches, caches)
        plen = int(prompt.shape[0])
        first = jnp.argmax(logits[0]).astype(jnp.int32)
        # the prefill token goes through the same retirement predicate as the
        # chunked path's fin_done (device-side, no sync): max_new=1 requests
        # emit exactly one token and an EOS first token stops the slot.
        done0 = ((first == self.eos_id)
                 | (remaining <= 1)
                 | (plen >= self.max_len - 1))
        self.pos = self.pos.at[slot].set(plen)
        self.cur_tok = self.cur_tok.at[slot].set(first)
        self.done = self.done.at[slot].set(done0)
        self.gen_count = self.gen_count.at[slot].set(1)
        self.max_new_arr = self.max_new_arr.at[slot].set(remaining)
        self.live[slot] = req
        self._pending_first.add(slot)

    # -- paged-KV write preparation (DESIGN.md §paged-kv) ---------------------

    def _apply_page_copies(self, pairs: list[tuple[int, int]]):
        """Apply COW (src, dst) page copies as ONE jitted gather/scatter over
        every pool leaf, before the tick dispatches. The pair list is padded
        to a power of two with garbage→garbage identity copies so compiled
        shapes stay bounded (≤ log2(pool) variants, in practice a handful)."""
        n = 1 << max(len(pairs) - 1, 0).bit_length()
        g = self.paged.garbage
        src = np.full((n,), g, np.int32)
        dst = np.full((n,), g, np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i], dst[i] = s, d
        self.caches = _copy_pages_cached(self.cfg)(
            self.caches, jnp.asarray(src), jnp.asarray(dst))

    def _cow_prepare(self, writes: list) -> list[int]:
        """COW-resolve the blocks this tick writes (``writes`` is a list of
        (slot, block-iterable)). Freshly mapped blocks need no copy — prefill
        chunks and decode/verify rows fully write them before any un-masked
        read. Slots the pool cannot cover even after trie eviction are
        FAILED-retired (pages released) and returned so the caller diverts
        them out of the tick. Idempotent per tick: the XLA-fallback retry
        re-runs it and finds every block already exclusive."""
        pairs, failed = [], []
        for s, blocks in writes:
            if self.live[s] is None:
                continue
            try:
                pairs += self.paged.ensure_writable(s, blocks)
            except PagePoolExhausted:
                req = self.live[s]
                self._event("page_pool_exhausted", rid=req.rid, slot=s)
                self._finish(s, req, R.Status.FAILED,
                             detail="page_pool_exhausted")
                failed.append(s)
        if pairs:
            self._event("cow_fork", pairs=len(pairs),
                        forks_total=self.paged.cow_forks)
            self._apply_page_copies(pairs)
        return failed

    def _prepare_tick_pages(self, selected, chunk, chunk_tok, chunk_off,
                            finishing, last_row, fin_pos, dec_active,
                            dec_span: int = 1):
        """Per-tick page preparation for the fused/speculative ticks: make
        every written block exclusive (chunk appends for selected prefilling
        slots, ``dec_span`` frontier rows per decoding slot) and return the
        device page table. Slots shed on pool exhaustion are diverted in
        place: chunk writes to the trash tail, decode/finishing masks off."""
        if self.paged is None:
            return None
        ps = self.paged.page_size
        writes = [(s, range(int(chunk_off[s]) // ps,
                            (int(chunk_off[s]) + chunk) // ps))
                  for s in selected]
        writes += [(s, range(int(self._pos_host[s]) // ps,
                             (int(self._pos_host[s]) + dec_span - 1) // ps + 1))
                   for s in range(self.slots) if dec_active[s]]
        for s in self._cow_prepare(writes):
            if s in selected:
                selected.remove(s)
            chunk_tok[s] = 0
            chunk_off[s] = self.trash_base
            finishing[s] = False
            last_row[s] = 0
            fin_pos[s] = 0
            dec_active[s] = False
        return jnp.asarray(self.paged.table)

    # -- the fused chunked-prefill + decode tick ------------------------------

    def _chunk_budget(self) -> int:
        """Effective chunk-token budget this tick: the base
        ``cfg.prefill_chunk_budget`` scaled by the highest SLO
        ``budget_weight`` among requests currently mid-prefill — the highest
        class present sets the prefill pace, so a lone batch/best_effort
        prompt appends fewer chunk rows per tick (shorter ticks → lower
        inter-token latency for co-batched decoding slots) while an
        interactive prompt always prefills at full pace. All weights 1.0
        (the default outside the pool) reproduce the pre-pool budget
        exactly; ``_plan_chunks`` still floors the result at one chunk so
        prefill always progresses."""
        w = max((self.live[s].budget_weight for s in range(self.slots)
                 if self._plan[s] is not None and self.live[s] is not None),
                default=1.0)
        return max(1, int(round(self.cfg.prefill_chunk_budget * w)))

    def _plan_chunks(self, prefilling: list, budget: int):
        """Select this tick's prompt-chunk work: the head slot's chunk size
        wins, same-size slots fill the token ``budget`` (≥ one chunk, so
        prefill always progresses), and finishing slots record where their
        first-token row and handoff position land. One definition shared by
        the plain fused tick and the speculative tick — the two must stay
        scheduling-identical for the bit-identity guarantee."""
        slots = self.slots
        head = self._plan[prefilling[0]]
        chunk = head.chunks[head.ci]
        budget = max(budget, chunk)
        selected = [s for s in prefilling
                    if self._plan[s].chunks[self._plan[s].ci] == chunk]
        selected = selected[: budget // chunk]
        chunk_tok = np.zeros((slots, chunk), np.int64)
        chunk_off = np.full((slots,), self.trash_base, np.int32)
        finishing = np.zeros((slots,), bool)
        last_row = np.zeros((slots,), np.int32)
        fin_pos = np.zeros((slots,), np.int32)
        for s in selected:
            p = self._plan[s]
            chunk_tok[s] = p.tokens[p.off: p.off + chunk]
            chunk_off[s] = p.off
            if p.ci == len(p.chunks) - 1:
                finishing[s] = True
                last_row[s] = p.true_len - 1 - p.off
                fin_pos[s] = p.true_len
        return chunk, selected, chunk_tok, chunk_off, finishing, last_row, fin_pos

    def _get_fused(self, chunk: int):
        fn = self._fused.get(chunk)
        if fn is None:
            fn = _fused_tick_step(
                self.cfg, chunk, mode=self.mode, attn_impl=self.attn_impl,
                eos_id=self.eos_id, max_len=self.max_len,
                cache_len=self.cache_len, trash_base=self.trash_base,
                fused=self.fused, guards=self.guards,
                debug_faults=self._debug_faults,
                paged=self.paged is not None)
            self._fused[chunk] = fn
        return fn

    def _maybe_raise_tick_fault(self):
        """Injected ``tick_exception``: emulate a failing Pallas dispatch.
        Fires only while the engine would still dispatch kernels
        (``attn_impl != "xla"``) and *before* the jitted call, so donated
        buffers survive and the sticky XLA fallback can retry the tick."""
        if self._fault_plan is None or self.attn_impl == "xla":
            return
        if self._fault_plan.at(self.tick_count, "tick_exception"):
            raise R.FaultInjected(
                f"injected tick exception @ tick {self.tick_count}")

    def _fault_masks(self, *kinds: str):
        """Traced injection operands for this tick ([] when no plan): one
        [slots] bool mask per kind. All-False masks make the injected
        ``where`` selects bitwise no-ops — no recompile, no drift."""
        if not self._debug_faults:
            return []
        return [jnp.asarray(self._fault_plan.slot_mask(
            self.tick_count, k, self.slots)) for k in kinds]

    def _fused_tick(self, prefilling: list) -> bool:
        self._maybe_raise_tick_fault()
        slots = self.slots
        with self._span("serve.tick.plan"):
            (chunk, selected, chunk_tok, chunk_off, finishing, last_row,
             fin_pos) = self._plan_chunks(prefilling, self._chunk_budget())
            dec_active = np.array(
                [self.live[s] is not None and self._plan[s] is None
                 for s in range(slots)])
            page_table = self._prepare_tick_pages(
                selected, chunk, chunk_tok, chunk_off, finishing, last_row,
                fin_pos, dec_active)
            self._note_chunks("fused", chunk, prefilling, selected,
                              dec_active)
            fused = self._get_fused(chunk)
            inputs = [jnp.asarray(x) for x in (dec_active, chunk_tok,
                                               chunk_off, finishing,
                                               last_row, fin_pos)]
            faults = self._fault_masks("nan")
        with self._span("serve.tick.launch"):
            (self.caches, self.cur_tok, self.pos, self.done, self.gen_count,
             packed) = fused(
                self.params, self.caches, self.cur_tok, self.pos, self.done,
                self.gen_count, self.max_new_arr, *inputs, page_table,
                *faults)
        state = self._transfer(packed)
        tok, _, done_, _ = state[:4]
        guard = state[4] if self.guards else np.zeros((slots,), np.int64)

        emitted = 0
        with self._span("serve.tick.retire"):
            if self.paged is not None:  # mirror the device frontier advance
                self._pos_host[dec_active] += 1
                self._pos_host[finishing] = fin_pos[finishing]
            for s in range(slots):
                req = self.live[s]
                if req is None:
                    continue
                if guard[s]:  # numerics guard tripped: discard the output
                    self._quarantine(s, req, guard[s])
                    continue
                if finishing[s]:
                    self._plan[s] = None
                    if self.paged is not None:  # intern the finished prefill
                        self.paged.insert_prefix(s)
                    req.generated.append(int(tok[s]))
                    emitted += 1
                    self._first_token(req)
                    if self.speculative:  # keep the drafter history current
                        self.hist = self.hist.at[s, int(fin_pos[s])].set(
                            int(tok[s]))
                    if done_[s]:
                        self._finish(s, req, self._terminal_status(req))
                elif s in selected:  # mid-prefill: advance the plan
                    p = self._plan[s]
                    p.off += chunk
                    p.ci += 1
                elif dec_active[s]:
                    req.generated.append(int(tok[s]))
                    emitted += 1
                    if done_[s]:
                        self._finish(s, req, self._terminal_status(req))
        self._tick_attrs["emitted"] = emitted
        return True

    # -- the speculative verify (+ optional prefill-chunk) tick ---------------

    def _get_spec(self, chunk: int | None):
        fn = self._spec.get(chunk)
        if fn is None:
            fn = _spec_tick_step(
                self.cfg, self.spec_gamma, chunk, mode=self.mode,
                attn_impl=self.attn_impl, eos_id=self.eos_id,
                max_len=self.max_len, cache_len=self.cache_len,
                trash_base=self.trash_base, fused=self.fused,
                guards=self.guards, debug_faults=self._debug_faults,
                paged=self.paged is not None)
            self._spec[chunk] = fn
        return fn

    def _spec_tick(self, prefilling: list) -> bool:
        """One speculative tick: draft+verify ``spec_gamma`` tokens for every
        decoding slot and (when ``prefilling`` is non-empty) append one prompt
        chunk per selected prefilling slot — the speculative twin of
        ``_fused_tick``/``_decode_tick``, still one host transfer."""
        self._maybe_raise_tick_fault()
        slots, gamma = self.slots, self.spec_gamma
        with self._span("serve.tick.plan"):
            dec_active = np.array(
                [self.live[s] is not None and self._plan[s] is None
                 for s in range(slots)])
            if prefilling:
                # verify tokens ride the same chunk-token budget as prefill
                # work: every decoding slot spends γ+1 chunk rows this tick,
                # the rest (at least one chunk, so prefill always
                # progresses) go to prompts
                (chunk, selected, chunk_tok, chunk_off, finishing, last_row,
                 fin_pos) = self._plan_chunks(
                    prefilling, self._chunk_budget()
                    - int(dec_active.sum()) * (gamma + 1))
            else:
                chunk = None
                selected = []
                chunk_tok = np.zeros((slots, 1), np.int64)
                chunk_off = np.full((slots,), self.trash_base, np.int32)
                finishing = np.zeros((slots,), bool)
                last_row = np.zeros((slots,), np.int32)
                fin_pos = np.zeros((slots,), np.int32)
            # verify writes γ+1 frontier rows per decoding slot; rejected
            # rows roll back by the pointer rewind alone — the pages they
            # landed in are already exclusive, so no page-table edit is ever
            # needed
            page_table = self._prepare_tick_pages(
                selected, chunk, chunk_tok, chunk_off, finishing, last_row,
                fin_pos, dec_active, dec_span=gamma + 1)
            self._note_chunks("spec", chunk or 0, prefilling, selected,
                              dec_active)
            fused = self._get_spec(chunk)
            inputs = [jnp.asarray(x) for x in (dec_active, chunk_tok,
                                               chunk_off, finishing,
                                               last_row, fin_pos)]
            faults = self._fault_masks("nan", "drafter_garbage")
        with self._span("serve.tick.launch"):
            (self.caches, self.hist, self.cur_tok, self.pos, self.done,
             self.gen_count, packed) = fused(
                self.params, self.caches, self.hist, self.cur_tok, self.pos,
                self.done, self.gen_count, self.max_new_arr, *inputs,
                page_table, *faults)
        state = self._transfer(packed)
        toks, n_out = state[: gamma + 1], state[gamma + 1]
        drafted_, done_ = state[gamma + 2], state[gamma + 3]
        guard = (state[gamma + 4] if self.guards
                 else np.zeros((slots,), np.int64))

        emitted = 0
        with self._span("serve.tick.retire"):
            if self.paged is not None:  # mirror the device frontier advance
                for s in range(slots):
                    if dec_active[s]:
                        self._pos_host[s] += int(n_out[s])
                self._pos_host[finishing] = fin_pos[finishing]
            for s in range(slots):
                req = self.live[s]
                if req is None:
                    continue
                if guard[s]:  # numerics guard tripped: discard the output
                    self._quarantine(s, req, guard[s])
                    continue
                if finishing[s]:
                    self._plan[s] = None
                    if self.paged is not None:  # intern the finished prefill
                        self.paged.insert_prefix(s)
                    req.generated.append(int(toks[0, s]))
                    emitted += 1
                    self._first_token(req)
                    if done_[s]:
                        self._finish(s, req, self._terminal_status(req))
                elif s in selected:  # mid-prefill: advance the plan
                    p = self._plan[s]
                    p.off += chunk
                    p.ci += 1
                elif dec_active[s]:
                    n, d = int(n_out[s]), int(drafted_[s])
                    req.generated.extend(int(toks[j, s]) for j in range(n))
                    emitted += n
                    req.spec_drafted += d
                    req.spec_accepted += min(n - 1, d)
                    self.spec_drafted_total += d
                    self.spec_accepted_total += min(n - 1, d)
                    if done_[s]:
                        self._finish(s, req, self._terminal_status(req))
            # acceptance-collapse watchdog: once enough drafts have been
            # offered to judge the workload, a collapsed acceptance rate
            # means verify ticks are pure overhead (γ+1-row forwards
            # emitting ~1 token) — stick to plain decode for the rest of
            # this engine's life.
            if (self.speculative and self.cfg.spec_disable_after > 0
                    and self.spec_drafted_total >= self.cfg.spec_disable_after
                    and self.spec_acceptance_rate
                    < self.cfg.spec_min_acceptance):
                self.speculative = False
                self._event("spec_disabled",
                            acceptance=round(self.spec_acceptance_rate, 4),
                            drafted=self.spec_drafted_total)
        self._tick_attrs["emitted"] = emitted
        return True

    def _decode_tick(self) -> bool:
        self._maybe_raise_tick_fault()
        with self._span("serve.tick.plan"):
            page_table = None
            if self.paged is not None:
                ps = self.paged.page_size
                # one frontier row written per live slot; empty slots write
                # the garbage page through their released (all-garbage)
                # table rows
                self._cow_prepare(
                    [(s, [int(self._pos_host[s]) // ps])
                     for s in range(self.slots) if self.live[s] is not None])
                page_table = jnp.asarray(self.paged.table)
            active_np = np.array([r is not None for r in self.live])
            active = jnp.asarray(active_np)
            faults = self._fault_masks("nan")
            self._tick_attrs.update(kind="decode", chunk=0,
                                    decoding=int(active_np.sum()),
                                    prefilling=0, selected=0, prefill_rows=0)
        with self._span("serve.tick.launch"):
            first_tok = self.cur_tok  # includes legacy prefills' tokens
            logits, self.caches = self._serve(
                self.params, {"tokens": self.cur_tok[:, None]}, self.caches,
                self.pos, page_table
            )
            extra = (self.caches,) if self.guards else ()
            (self.cur_tok, self.pos, self.done, self.gen_count,
             packed) = self._advance(
                logits, first_tok, self.pos, self.done, self.gen_count,
                self.max_new_arr, active, *extra, *faults,
            )
        state = self._transfer(packed)
        first, nxt, _, done, _, entry_done = state[:6]
        guard = (state[6] if self.guards
                 else np.zeros((self.slots,), np.int64))
        emitted = 0
        with self._span("serve.tick.retire"):
            if self.paged is not None:  # mirror the device frontier advance
                self._pos_host[active_np] += 1
            for slot, req in enumerate(self.live):
                if req is None:
                    continue
                if guard[slot]:  # numerics guard tripped: discard the output
                    self._quarantine(slot, req, guard[slot])
                    continue
                if slot in self._pending_first:
                    req.generated.append(int(first[slot]))
                    emitted += 1
                    self._first_token(req)
                    self._pending_first.discard(slot)
                    if entry_done[slot]:  # retired on its prefill token
                        self._finish(slot, req, self._terminal_status(req))
                        continue
                req.generated.append(int(nxt[slot]))
                emitted += 1
                if done[slot]:
                    self._finish(slot, req, self._terminal_status(req))
        self._tick_attrs["emitted"] = emitted
        return True

    # -- tick tracing (DESIGN.md §tracing) -----------------------------------

    def _span(self, name: str, **attrs) -> tracing.OpenSpan:
        return tracing.span(name, replica=self.replica_id, **attrs)

    def _transfer(self, packed) -> np.ndarray:
        """The tick's one host transfer (``serve.tick.wait``); its end is
        when this tick's tokens were read back."""
        with self._span("serve.tick.wait") as wait:
            state = jax.device_get(packed)
        self._t_read_ns = wait.end_ns
        return state

    def _note_chunks(self, kind: str, chunk: int, prefilling: list,
                     selected: list, dec_active: np.ndarray) -> None:
        """The ``serve.tick`` attributes of a tick that carries prompt
        chunks."""
        rows = sum(min(chunk, self._plan[s].true_len - self._plan[s].off)
                   for s in selected if self.live[s] is not None)
        self._tick_attrs.update(kind=kind, chunk=chunk,
                                decoding=int(dec_active.sum()),
                                prefilling=len(prefilling),
                                selected=len(selected), prefill_rows=rows)

    def _first_token(self, req: Request) -> None:
        """A request's first token was read back: its ``serve.prefill``
        span, from its latest admission."""
        if req.t_first_ns is not None:  # resumed after preemption
            return
        req.t_first_ns = self._t_read_ns
        tracing.interval("serve.prefill", req.t_admitted_ns or self._t_read_ns,
                         self._t_read_ns, rid=req.rid, replica=self.replica_id)

    def _dispatch(self) -> bool:
        """Route one tick to the right jit family (recomputed fresh so a
        fallback retry sees post-quarantine/post-preemption slot state)."""
        prefilling = [s for s in range(self.slots) if self._plan[s] is not None]
        if self.speculative:
            decoding = any(self.live[s] is not None and self._plan[s] is None
                           for s in range(self.slots))
            if prefilling and not decoding:
                # pure-prefill tick: nothing to verify — the plain fused tick
                # does the chunk work without paying a discarded γ+1-row
                # verify forward (it keeps the drafter history current via
                # its finishing-slot hook below)
                return self._fused_tick(prefilling)
            return self._spec_tick(prefilling)
        if prefilling:
            return self._fused_tick(prefilling)
        return self._decode_tick()

    def _tick_fallback(self, exc: Exception) -> bool:
        """Sticky kernel→XLA fallback: a raising tick (an injected Pallas
        failure, or a real one) flips ``attn_impl`` to the dense XLA form,
        rebuilds the tick jits, and retries the tick once. A tick that fails
        even on the fallback — or whose failed jit already invalidated its
        donated cache buffers — degrades to ``_fail_all_live`` so the engine
        keeps serving the queue."""
        detail = f"{type(exc).__name__}: {exc}"
        if not self.xla_fallback and self.attn_impl != "xla":
            self._event("xla_fallback", error=detail[:200])
            self.xla_fallback = True
            self.attn_impl = "xla"
            self._fused = {}
            self._spec = {}
            self._serve = _serve_step_cached(self.cfg, self.mode, "xla",
                                             self.fused)
            leaves = jax.tree.leaves(self.caches)
            if self.hist is not None:
                leaves.append(self.hist)
            if any(getattr(x, "is_deleted", lambda: False)() for x in leaves):
                detail = "donated_buffers_invalidated: " + detail
            else:
                try:
                    return self._dispatch()
                except Exception as e2:  # noqa: BLE001
                    detail = f"{type(e2).__name__}: {e2}"
        self._fail_all_live(detail[:200])
        return True

    def step(self):
        """One scheduler tick: expire/cancel, admit queued requests (highest
        priority first, preempting under cache pressure), then one fused
        chunked-prefill + decode step (or a plain decode / speculative-verify
        step). One host transfer either way. ``step`` never raises — a
        failing tick degrades through the sticky XLA fallback and, last,
        ``FAILED`` retirements (DESIGN.md §resilience).

        With ``on_emit``/``on_finish`` set (DESIGN.md §serving-frontdoor),
        every request that was in the queue or a slot when the tick started
        is re-inspected after it: new tokens fire ``on_emit(req, tokens)``
        and a terminal transition fires ``on_finish(req)`` — tokens strictly
        before the finish, so a stream's terminal event always trails its
        last token. Every path that can end a request inside a tick (expiry,
        cancellation, quarantine, retirement, ``_fail_all_live``) flows
        through this one delivery point; requests rejected by ``submit()``
        itself never reach it (the caller sees the rejection synchronously).
        """
        watch = None
        if self.on_emit is not None or self.on_finish is not None:
            watch = [(r, len(r.generated)) for r in
                     self.queue + [x for x in self.live if x is not None]]
        tick = self.tick_count
        with self._span("serve.tick", tick=tick) as span:
            with self._on_device():
                out = self._step_impl()
            if watch is not None:
                with self._span("serve.tick.emit"):
                    for req, n in watch:
                        if self.on_emit is not None and len(req.generated) > n:
                            self.on_emit(req, req.generated[n:])
                        if self.on_finish is not None and req.done:
                            self.on_finish(req)
            if out:
                span.set(**self._tick_attrs)
            else:  # nothing dispatched: no tick
                span.discard()
        # the tick's span is the straggler watchdog's tick time
        if out and self.straggler.record(tick, span.duration_s):
            self._event("straggler", tick=tick,
                        duration_s=round(span.duration_s, 4))
        return out

    def _step_impl(self):
        tick = self.tick_count
        with self._span("serve.tick.admit") as admit:
            self._admitted = 0
            self._expire_and_cancel(self._clock())
            if self._fault_plan is not None:
                # cache_growth: the slot's cache cannot hold the request —
                # the engine's graceful answer is a CACHE_EXHAUSTED
                # retirement with every already-emitted token kept.
                for f in self._fault_plan.at(tick, "cache_growth"):
                    for s in (range(self.slots) if f.slot is None
                              else [f.slot]):
                        if 0 <= s < self.slots and self.live[s] is not None:
                            self._event("cache_growth_fault",
                                        rid=self.live[s].rid, slot=s)
                            self._finish(s, self.live[s],
                                         R.Status.CACHE_EXHAUSTED,
                                         detail="fault_injected")
            self._admission()
            admit.set(admitted=self._admitted)
        if all(r is None for r in self.live):
            return False
        # a dispatched tick's serve.tick attributes; the tick form fills
        # them in, and a tick that fails before it plans keeps these
        self._tick_attrs = dict(kind="failed", chunk=0, decoding=0,
                                prefilling=0, selected=0, prefill_rows=0,
                                emitted=0)
        try:
            if self._fault_plan is not None:
                for f in self._fault_plan.at(tick, "slow_tick"):
                    self._event("slow_tick_fault", duration_s=f.duration_s)
                    time.sleep(f.duration_s)
            try:
                out = self._dispatch()
                self.consecutive_tick_failures = 0  # clean tick: gate resets
            except Exception as exc:  # noqa: BLE001 — the tick must not raise
                # counted even when the sticky XLA fallback recovers the
                # tick: repeated entries into the exception path are the
                # pool's drain signal (DESIGN.md §replica-pool)
                self.consecutive_tick_failures += 1
                out = self._tick_fallback(exc)
        finally:
            self.tick_count += 1
        return out

    def run(self):
        while self.queue or any(r is not None for r in self.live):
            if not self.step():
                break


def _advance(logits, first_tok, pos, done, gen_count, max_new, active, *extra,
             eos_id: int, max_len: int, guards: bool = False,
             debug_faults: bool = False, axes_tree=None):
    """Pure per-tick state transition for decode-only ticks (jitted once per
    engine).

    Greedy-samples the batch, advances active slots' positions/counters, and
    folds the retirement conditions (EOS, budget, cache-full) into ``done`` —
    all device-side. Returns the new state plus one packed int32 [6, slots]
    array (prefill token, next token, position, done, count, done-at-entry —
    the last row tells the scheduler a slot retired on its prefill token, so
    its decode output this tick must be discarded) so the scheduler reads
    everything back in a single transfer. With ``guards`` the packed array
    grows one guard-flag row ([7, slots]; resilience.GUARD_* bitmask over
    this tick's logits and freshly written quant-scale rows) and ``extra``
    leads with the post-step cache tree; with ``debug_faults`` ``extra`` ends
    with the [slots] NaN-injection mask.
    """
    caches = extra[0] if guards else None
    if debug_faults:
        fault_nan = extra[-1]
        logits = jnp.where(fault_nan[:, None],
                           jnp.asarray(jnp.nan, logits.dtype), logits)
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    inc = active.astype(jnp.int32)
    new_pos = pos + inc
    new_count = gen_count + inc
    new_done = done | (active & _retire(next_tok, new_pos, new_count, max_new,
                                        eos_id=eos_id, max_len=max_len))
    rows = [
        first_tok, next_tok, new_pos, new_done.astype(jnp.int32), new_count,
        done.astype(jnp.int32),
    ]
    if guards:
        lbad = R.logits_guard(logits, where=active)
        sbad = R.scale_guard(caches, axes_tree, pos[:, None], active[:, None])
        rows.append(lbad.astype(jnp.int32) * R.GUARD_LOGITS
                    + sbad.astype(jnp.int32) * R.GUARD_SCALES)
    packed = jnp.stack(rows)
    return next_tok, new_pos, new_done, new_count, packed


def _retire(next_tok, new_pos, new_count, max_new, *, eos_id: int, max_len: int):
    """The one retirement predicate both tick paths share: EOS emitted,
    generation budget spent, or cache full."""
    return ((next_tok == eos_id)
            | (new_count >= max_new)
            | (new_pos >= max_len - 1))


def _prefill_handoff(first_logits, finishing, fin_pos, new_tok, new_pos,
                     new_count, new_done, max_new, *, eos_id: int,
                     max_len: int):
    """Prefill→decode handoff, one definition for the plain fused tick and
    the speculative tick: finishing slots start decoding from their chunk's
    last real row (count 1, pos = true prompt length), with the first token
    pushed through the same retirement predicate as every decode emission.
    Returns (first_tok, new_tok, new_pos, new_count, new_done)."""
    first_tok = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
    new_tok = jnp.where(finishing, first_tok, new_tok)
    new_pos = jnp.where(finishing, fin_pos, new_pos)
    new_count = jnp.where(finishing, jnp.int32(1), new_count)
    fin_done = _retire(first_tok, fin_pos, jnp.int32(1), max_new,
                       eos_id=eos_id, max_len=max_len)
    new_done = jnp.where(finishing, fin_done, new_done)
    return first_tok, new_tok, new_pos, new_count, new_done


def live_cache_state(caches, cfg, frontier):
    """Canonical *live* view of a cache tree for state-equality checks: every
    ``act_kv_seq`` row at/past the per-slot ``frontier`` is zeroed (int8 scale
    side arrays included — their axes tree carries the same tag).

    This encodes the rollback invariant (DESIGN.md §speculative): rows past a
    slot's frontier are dead — never read, next to be overwritten — so two
    engine states are equivalent iff they agree under this mask. Used by the
    rollback property tests; axis selection is path-based like
    ``_resize_caches``.
    """
    _, axes_tree = Tr.cache_specs(cfg, 1, 1)

    def rec(c, a):
        if isinstance(c, dict):
            return {k: rec(c[k], a[k]) for k in c}
        if "act_kv_seq" not in a:
            return c
        return ternary.mask_past_frontier(
            c, frontier, seq_axis=a.index("act_kv_seq"),
            batch_axis=a.index("act_batch"))

    return rec(caches, axes_tree)


# Module-level compiled-step caches (configs are frozen dataclasses, hence
# hashable): repeat ServingEngine instances with the same geometry — tests,
# benchmarks, restarted servers — reuse compiled ticks instead of retracing.
_SERVE_STEP_CACHE: dict = {}
_ADVANCE_CACHE: dict = {}
_FUSED_TICK_CACHE: dict = {}
_SPEC_TICK_CACHE: dict = {}
_COPY_PAGES_CACHE: dict = {}


def _copy_pages_cached(cfg):
    """One jitted COW page copy per config: gather the ``src`` pool rows of
    every paged leaf, scatter them at ``dst``. Only leaves whose axes carry
    ``kv_pages`` move; the caller pads (src, dst) to a power of two with
    garbage self-copies so this compiles a handful of shapes, ever."""
    fn = _COPY_PAGES_CACHE.get(cfg)
    if fn is None:
        axes_tree = Tr.cache_specs(cfg, 1, 1, kv_pages=1)[1]

        def copy(caches, src, dst):
            def rec(c, a):
                if isinstance(c, dict):
                    return {k: rec(c[k], a[k]) for k in c}
                return c.at[dst].set(c[src]) if "kv_pages" in a else c

            return rec(caches, axes_tree)

        fn = jax.jit(copy, donate_argnums=(0,))
        _COPY_PAGES_CACHE[cfg] = fn
    return fn


def _serve_step_cached(cfg, mode: str, attn_impl: str, fused: bool | None = None):
    key_t = (cfg, mode, attn_impl, fused)
    fn = _SERVE_STEP_CACHE.get(key_t)
    if fn is None:
        # caches are donated (matching the fused tick) so decode-only ticks
        # update the KV cache in place instead of copying it every step —
        # the engine reassigns self.caches from the result each tick.
        fn = jax.jit(Lyr.scoped("tick/decode")(
            make_serve_step(cfg, mode=mode, attn_impl=attn_impl,
                            fused=fused)), donate_argnums=(2,))
        _SERVE_STEP_CACHE[key_t] = fn
    return fn


def _advance_cached(cfg, eos_id: int, max_len: int, guards: bool = False,
                    debug_faults: bool = False, paged: bool = False):
    key_t = (cfg, eos_id, max_len, guards, debug_faults, paged)
    fn = _ADVANCE_CACHE.get(key_t)
    if fn is None:
        # the axes tree is static closure data (needed only by the scale
        # guard's path-based cache walk); paged pool leaves carry no
        # act_kv_seq axis, so the scale guard skips them by construction
        axes_tree = (Tr.cache_specs(cfg, 1, 1, kv_pages=1 if paged else None)[1]
                     if guards else None)
        fn = jax.jit(partial(Lyr.scoped("tick/decode")(_advance),
                             eos_id=eos_id, max_len=max_len, guards=guards,
                             debug_faults=debug_faults, axes_tree=axes_tree))
        _ADVANCE_CACHE[key_t] = fn
    return fn


def _fused_tick_step(cfg, chunk: int, *, mode: str, attn_impl: str,
                     eos_id: int, max_len: int, cache_len: int,
                     trash_base: int, fused: bool | None = None,
                     guards: bool = False, debug_faults: bool = False,
                     paged: bool = False):
    """The engine's one-jit scheduler tick for chunk size ``chunk``: decode
    every decoding slot AND append one prompt chunk per selected prefilling
    slot — inactive slots are diverted into the cache's trash tail, keeping
    the call fixed-shape with no masking inside the kernels. ``guards`` adds
    one guard-flag row to the packed array ([5, slots]); ``debug_faults``
    adds one trailing [slots] NaN-injection operand."""
    key_t = (cfg, chunk, mode, attn_impl, eos_id, max_len, cache_len,
             trash_base, fused, guards, debug_faults, paged)
    fn = _FUSED_TICK_CACHE.get(key_t)
    if fn is not None:
        return fn
    axes_tree = (Tr.cache_specs(cfg, 1, 1, kv_pages=1 if paged else None)[1]
                 if guards else None)

    def fused(params, caches, cur_tok, pos, done, gen_count, max_new,
              dec_active, chunk_tok, chunk_off, finishing, last_row, fin_pos,
              page_table, *fault):
        # 1. one decode token for every decoding slot (others diverted to
        #    the trash row — fixed-shape batch, garbage ignored). The decode
        #    pass piggybacks on every fused tick even when dec_active is
        #    all-False (cold start, all slots prefilling): a prefill-only
        #    variant would save that one forward but double the compiled
        #    prefill shapes, and diverted slots' frontier (cache_len - 1)
        #    defeats block skipping only for their own rows.
        dpos = jnp.where(dec_active, pos, jnp.int32(cache_len - 1))
        dec_logits, caches = Tr.decode_step(
            params, {"tokens": cur_tok[:, None]}, caches, dpos, cfg,
            mode=mode, attn_impl=attn_impl, fused=fused,
            page_table=page_table)
        # 2. one chunk bucket appended at each selected slot's frontier
        #    (idle slots write into the trash tail); the LM head runs only on
        #    each slot's last_row hidden state, not all C chunk rows
        first_logits, caches = Tr.prefill_chunk_step(
            params, {"tokens": chunk_tok}, caches, chunk_off, cfg,
            mode=mode, attn_impl=attn_impl, last_row=last_row,
            prefix_limit=trash_base, fused=fused, page_table=page_table)
        if debug_faults:
            # NaN activation at the guard's observation point; an all-False
            # mask makes both selects bitwise no-ops
            (fault_nan,) = fault
            dec_logits = jnp.where(
                fault_nan[:, None],
                jnp.asarray(jnp.nan, dec_logits.dtype), dec_logits)
            first_logits = jnp.where(
                fault_nan[:, None],
                jnp.asarray(jnp.nan, first_logits.dtype), first_logits)
        next_dec = jnp.argmax(dec_logits, axis=-1).astype(jnp.int32)
        # 3. decode advance (the _advance transition, masked to dec_active)
        inc = dec_active.astype(jnp.int32)
        new_pos = pos + inc
        new_count = gen_count + inc
        new_done = done | (dec_active & _retire(
            next_dec, new_pos, new_count, max_new,
            eos_id=eos_id, max_len=max_len))
        new_tok = jnp.where(dec_active, next_dec, cur_tok)
        # 4. prefill→decode handoff (shared with the speculative tick)
        _, new_tok, new_pos, new_count, new_done = _prefill_handoff(
            first_logits, finishing, fin_pos, new_tok, new_pos, new_count,
            new_done, max_new, eos_id=eos_id, max_len=max_len)
        rows = [new_tok, new_pos, new_done.astype(jnp.int32), new_count]
        if guards:
            # logits at rows that emit this tick; scales at rows written
            # live this tick (decode row iff decoding, chunk rows iff not
            # trash-diverted) — stale rows past a frontier may hold a
            # quarantined predecessor's garbage and must not be judged
            lbad = (R.logits_guard(dec_logits, where=dec_active)
                    | R.logits_guard(first_logits, where=finishing))
            crows = (chunk_off[:, None]
                     + jnp.arange(chunk, dtype=jnp.int32)[None, :])
            grows = jnp.concatenate([dpos[:, None], crows], axis=1)
            gvalid = jnp.concatenate(
                [dec_active[:, None],
                 jnp.broadcast_to((chunk_off < trash_base)[:, None],
                                  crows.shape)], axis=1)
            sbad = R.scale_guard(caches, axes_tree, grows, gvalid)
            rows.append(lbad.astype(jnp.int32) * R.GUARD_LOGITS
                        + sbad.astype(jnp.int32) * R.GUARD_SCALES)
        packed = jnp.stack(rows)
        return caches, new_tok, new_pos, new_done, new_count, packed

    fn = jax.jit(Lyr.scoped("tick/fused")(fused), donate_argnums=(1,))
    _FUSED_TICK_CACHE[key_t] = fn
    return fn


def _spec_tick_step(cfg, gamma: int, chunk: int | None, *, mode: str,
                    attn_impl: str, eos_id: int, max_len: int, cache_len: int,
                    trash_base: int, fused: bool | None = None,
                    guards: bool = False, debug_faults: bool = False,
                    paged: bool = False):
    """The speculative engine's one-jit tick: draft + verify ``gamma`` tokens
    for every decoding slot, and — when ``chunk`` is a size, the mixed-tick
    form — append one prompt chunk per selected prefilling slot. Compiled
    shapes stay bounded: one jit per (chunk|None, γ) pair.

    Per decoding slot the tick emits ``n ∈ [1, γ+1]`` tokens: the longest
    accepted draft prefix plus one model correction, cut short at the first
    token that retires the slot (EOS mid-acceptance, budget, cache-full) by
    walking ``_retire`` per micro-step — so the emitted stream is exactly
    what ``n`` plain decode ticks would have produced. The frontier advances
    by ``n`` only: rejected rows at ``pos+n..pos+γ`` are rolled back by the
    pointer rewind (never read, overwritten by the next tick's chunk).
    """
    key_t = (cfg, gamma, chunk, mode, attn_impl, eos_id, max_len, cache_len,
             trash_base, fused, guards, debug_faults, paged)
    fn = _SPEC_TICK_CACHE.get(key_t)
    if fn is not None:
        return fn
    drafter = Sp.make_drafter(cfg, gamma=gamma)
    axes_tree = (Tr.cache_specs(cfg, 1, 1, kv_pages=1 if paged else None)[1]
                 if guards else None)

    def tick(params, caches, hist, cur_tok, pos, done, gen_count, max_new,
             dec_active, chunk_tok, chunk_off, finishing, last_row, fin_pos,
             page_table, *fault):
        # 1. draft γ candidates per slot from its device-resident history
        #    (prompt-lookup n-gram match — no host round-trip, no model pass)
        drafts = drafter(hist, pos)
        if debug_faults:
            fault_nan, fault_draft = fault
            # drafter_garbage: derange the drafts (still valid ids) so the
            # verify rejects them — acceptance collapse, not corruption
            drafts = R.scramble_tokens(drafts, fault_draft, cfg.vocab_size)
        ver_tok = jnp.concatenate([cur_tok[:, None], drafts], axis=1)
        ver_off = jnp.where(dec_active, pos, jnp.int32(trash_base))
        # 2. verify: the γ+1 chunk [cur_tok, drafts] appends at the frontier
        #    (idle/prefilling slots diverted to the trash tail) and returns
        #    logits at every row — one weight/cache stream for γ+1 positions
        ver_logits, caches = Tr.verify_chunk_step(
            params, {"tokens": ver_tok}, caches, ver_off, cfg, mode=mode,
            prefix_limit=trash_base, fused=fused, page_table=page_table)
        if debug_faults:
            ver_logits = jnp.where(
                fault_nan[:, None, None],
                jnp.asarray(jnp.nan, ver_logits.dtype), ver_logits)
        targets, k = Sp.accept_tokens(drafts, ver_logits)
        # 3. sequential-equivalent emission: micro-step j emits targets[:, j]
        #    (valid while j <= k), stopping at the first token that retires
        #    the slot — the same _retire predicate plain decode applies per
        #    tick, so EOS/budget/cache-full land mid-acceptance identically
        j = jnp.arange(gamma + 1, dtype=jnp.int32)[None, :]
        pos_j = pos[:, None] + j + 1
        cnt_j = gen_count[:, None] + j + 1
        retire_j = _retire(targets, pos_j, cnt_j, max_new[:, None],
                           eos_id=eos_id, max_len=max_len)
        stop_before = jnp.cumsum(
            jnp.pad(retire_j[:, :-1], ((0, 0), (1, 0))).astype(jnp.int32),
            axis=1) > 0
        emit = (j <= k[:, None]) & ~stop_before & dec_active[:, None]
        n_emit = emit.sum(axis=1).astype(jnp.int32)
        last_tok = jnp.take_along_axis(
            targets, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
        new_tok = jnp.where(dec_active, last_tok, cur_tok)
        # frontier rewind IS the rollback: rows pos+n_emit..pos+γ go dead
        new_pos = pos + n_emit
        new_count = gen_count + n_emit
        new_done = done | (retire_j & emit).any(axis=1)
        # 4. append the emissions to the drafter history (token j lands at
        #    hist[pos+1+j]) — masked select, same form as append_kv_cache
        hidx = jnp.arange(hist.shape[1], dtype=jnp.int32)[None, :]
        rel = hidx - (pos[:, None] + 1)
        relc = jnp.clip(rel, 0, gamma)
        write = (jnp.take_along_axis(emit, relc, axis=1)
                 & (rel >= 0) & (rel <= gamma))
        hist = jnp.where(write, jnp.take_along_axis(targets, relc, axis=1),
                         hist)
        if chunk is not None:
            # 5. mixed tick: one prompt chunk per selected prefilling slot —
            #    identical to _fused_tick_step's prefill phase (disjoint slot
            #    sets, so ordering against the verify append is immaterial)
            first_logits, caches = Tr.prefill_chunk_step(
                params, {"tokens": chunk_tok}, caches, chunk_off, cfg,
                mode=mode, attn_impl=attn_impl, last_row=last_row,
                prefix_limit=trash_base, fused=fused, page_table=page_table)
            if debug_faults:
                first_logits = jnp.where(
                    fault_nan[:, None],
                    jnp.asarray(jnp.nan, first_logits.dtype), first_logits)
            first_tok, new_tok, new_pos, new_count, new_done = _prefill_handoff(
                first_logits, finishing, fin_pos, new_tok, new_pos, new_count,
                new_done, max_new, eos_id=eos_id, max_len=max_len)
            oh = (hidx == fin_pos[:, None]) & finishing[:, None]
            hist = jnp.where(oh, first_tok[:, None], hist)
            emit0 = jnp.where(finishing, first_tok, targets[:, 0])
            n_out = jnp.where(finishing, jnp.int32(1), n_emit)
        else:
            emit0 = targets[:, 0]
            n_out = n_emit
        # drafts *chargeable* to acceptance stats: only positions the budget
        # and cache-full predicates could ever have emitted — a max_new=1
        # request must not report 0% acceptance for drafts it never got to
        # use (EOS truncation still counts: that IS a model-vs-draft outcome)
        window = jnp.minimum(jnp.int32(gamma + 1),
                             jnp.minimum(max_new - gen_count,
                                         jnp.int32(max_len - 1) - pos))
        drafted = jnp.clip(window - 1, 0, gamma) * dec_active.astype(jnp.int32)
        emit_rows = jnp.concatenate([emit0[:, None], targets[:, 1:]], axis=1)
        tail = [n_out[None], drafted[None], new_done.astype(jnp.int32)[None]]
        if guards:
            # logits at emitting rows; scales at this tick's written rows
            # (γ+1 verify rows iff decoding, chunk rows iff not diverted)
            lbad = R.logits_guard(ver_logits, where=dec_active)
            vrows = (ver_off[:, None]
                     + jnp.arange(gamma + 1, dtype=jnp.int32)[None, :])
            grows, gvalid = vrows, jnp.broadcast_to(
                dec_active[:, None], vrows.shape)
            if chunk is not None:
                lbad |= R.logits_guard(first_logits, where=finishing)
                crows = (chunk_off[:, None]
                         + jnp.arange(chunk, dtype=jnp.int32)[None, :])
                grows = jnp.concatenate([grows, crows], axis=1)
                gvalid = jnp.concatenate(
                    [gvalid, jnp.broadcast_to(
                        (chunk_off < trash_base)[:, None], crows.shape)],
                    axis=1)
            sbad = R.scale_guard(caches, axes_tree, grows, gvalid)
            tail.append((lbad.astype(jnp.int32) * R.GUARD_LOGITS
                         + sbad.astype(jnp.int32) * R.GUARD_SCALES)[None])
        packed = jnp.concatenate([emit_rows.T.astype(jnp.int32), *tail])
        return caches, hist, new_tok, new_pos, new_done, new_count, packed

    fn = jax.jit(Lyr.scoped("tick/spec")(tick), donate_argnums=(1, 2))
    _SPEC_TICK_CACHE[key_t] = fn
    return fn
