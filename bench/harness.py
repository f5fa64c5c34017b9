"""One run of one cell: build, serve, load, measure, check, report.

A cell is found by name: its entry in ``BENCHMARK.json`` names a
configuration and a traffic mix, and ``bench/cells/<cell>.json`` gives its
slots, ``max_len``, its clients (closed loop) or rate (open loop), its
correctness sample and limits. The configuration is
``bench/configs/<config>.json``, the mix ``bench/traffic/<mix>.json``, and
every metric is read by ``bench/metrics/<metric>.py``. A later cell, mix,
configuration or metric is a new file and a new entry; nothing here
changes.

The timed path is the program's normal one: ``repro.launch.server``'s
``build_backend`` wrapped in ``ServingServer`` on ``127.0.0.1``, so
HTTP/SSE front door -> ``ServingEngine`` ticks -> packed Pallas kernels,
loaded from an in-process asyncio client.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

import client as C
import devtrace
import reference
import traffic as T
import weights as W
import work

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_S = 5.0  # length of the traced part of a --trace 1 window
KV_BITS = {"bf16": 16, "int8": 8}  # a configuration's kv_cache_dtype, in bits
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class SetupError(RuntimeError):
    """The run cannot be measured; it prints no result."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict  # the workload's entry in BENCHMARK.json
    spec: dict  # bench/cells/<name>.json
    config: dict  # bench/configs/<config>.json
    mix: dict  # bench/traffic/<traffic>.json


def load_benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(name: str) -> Cell:
    bench = load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    b = os.path.join(ROOT, "bench")
    return Cell(name, entry, _json(os.path.join(b, "cells", name + ".json")),
                _json(os.path.join(b, "configs", entry["config"] + ".json")),
                T.load_mix(os.path.join(b, "traffic",
                                        entry["traffic"] + ".json")))


def metrics_for(cell: Cell, trace: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    bench = load_benchmark()
    e2e = [m for m in bench["end_to_end"]
           if cell.name in m.get("workloads", [cell.name])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell.name in m.get("workloads", [cell.name])
            and m["moves"] in names]


def reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``; a name
    split by cell kind, ``<base>.<kind>``, falls back to ``<base>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, "metrics", name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- instrumentation ---------------------------------------------------------


class Ticks:
    """Wraps the engine instance's ``_dispatch`` to record, for each tick,
    its start time and the work it carries (``work.py``'s tick record),
    and puts ``TraceAnnotation`` spans around ``step``, ``_admission``,
    ``_dispatch`` and the token hook. Benchmark-side only: no program
    file changes."""

    def __init__(self, eng):
        import jax

        self.records: list[dict] = []
        ann = jax.profiler.TraceAnnotation

        def span(name, fn):
            def wrapped(*a, **k):
                with ann(name):
                    return fn(*a, **k)
            return wrapped

        dispatch = eng._dispatch

        def recorded():
            self.records.append(self.describe(eng))
            return dispatch()

        eng._dispatch = span("bench.dispatch", recorded)
        eng._admission = span("bench.admission", eng._admission)
        eng.step = span("bench.step", eng.step)
        if eng.on_emit is not None:
            eng.on_emit = span("bench.emit", eng.on_emit)

    @staticmethod
    def describe(eng) -> dict:
        t = time.perf_counter()
        dec = [len(r.prompt) + len(r.generated)
               for r, p in zip(eng.live, eng._plan)
               if r is not None and p is None]
        prefilling = [s for s in range(eng.slots) if eng._plan[s] is not None]
        pre, fin = [], 0
        if prefilling:
            chunk, selected, _, off, finishing, _, _ = eng._plan_chunks(
                prefilling, eng._chunk_budget())
            pre = [(int(off[s]), min(chunk, eng._plan[s].true_len
                                     - int(off[s]))) for s in selected]
            fin = int(finishing.sum())
        return {"t": t, "dec": dec, "pre": pre, "emit": len(dec) + fin}


class CompileCounter:
    """Counts backend compiles and persistent-cache loads as they happen."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.by_event = dict.fromkeys(COMPILE_EVENTS, 0)
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_a, **_k):
        if name in COMPILE_EVENTS:
            self.n += 1
            self.by_event[name] += 1


# -- the run -----------------------------------------------------------------


@dataclasses.dataclass
class Window:
    t_ready: float = 0.0  # the server's warmup done
    t_open: float = 0.0
    t_close: float = 0.0
    trace_dir: str | None = None
    trace_host: tuple = (0.0, 0.0)  # perf_counter at the traced span's ends
    compiles: int = 0
    setup_compiles: dict = dataclasses.field(default_factory=dict)
    events: list = dataclasses.field(default_factory=list)
    kv_bits: int = 0  # narrowest live cache leaf, after warmup and at close


def server_args(cell: Cell, seed: int, smoke: bool, kv: str | None = None):
    """The launcher's arguments for this cell; ``kv`` switches on another
    KV-cache dtype than the configuration's (the control only)."""
    from repro.launch import server as launcher

    c, s = cell.config, cell.spec
    argv = ["--arch", c["arch"], "--seed", str(seed % 2**31),
            "--slots", str(s["slots"]), "--max-len", str(s["max_len"]),
            "--kv-cache-dtype", kv or c["kv_cache_dtype"], "--queue-cap", "0"]
    return launcher.parse_args(argv + (["--smoke"] if smoke else []))


def check_config(cfg, c: dict) -> None:
    """The registry's configuration must be the file's, width for width."""
    pairs = {"d_model": "hidden_size", "n_layers": "num_hidden_layers",
             "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
             "d_ff": "intermediate_size", "vocab_size": "vocab_size",
             "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
             "kv_cache_dtype": "kv_cache_dtype", "kv_layout": "kv_layout"}
    bad = {a: (getattr(cfg, a), c[b]) for a, b in pairs.items()
           if getattr(cfg, a) != c[b]}
    if bad or cfg.family != "dense" or cfg.padded_vocab != cfg.vocab_size:
        raise SetupError(f"program config differs from the file: {bad}")


def smoke_config(cfg) -> dict:
    """A configuration dict for the registry's smoke twin (tests only)."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "kv_cache_dtype": cfg.kv_cache_dtype,
            "kv_layout": cfg.kv_layout}


def engine_faults(st: dict, *, tpu: bool) -> list:
    """No hidden fallback in the engine's ``stats()``: the kernels run and
    nothing failed over."""
    bad = [f"{e['kind']}: {e.get('error') or e.get('detail')}"
           for e in st["events"] if e["kind"] in ("xla_fallback",
                                                  "tick_failure")]
    if tpu and st["attn_impl"] != "kernel":
        bad.append(f"attn_impl={st['attn_impl']!r}, not 'kernel'")
    return bad


def cache_bits(eng) -> int:
    """Bits of the narrowest number type among the engine's live cache
    leaves: the precision the served KV rows are stored at."""
    import jax
    import jax.numpy as jnp

    bits = [jnp.finfo(x.dtype).bits if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo(x.dtype).bits
            for x in jax.tree.leaves(eng.caches)
            if jnp.issubdtype(x.dtype, jnp.number)]
    return min(bits) if bits else 0


def make_checks(chk: dict, reading: dict, kv_bits: int, want_bits: int):
    """Each number the correctness check compares, beside its limit.

    ``reading`` is the reference's verdict on a run's tokens (``max_gap``,
    ``tokens``); ``kv_bits`` the precision the run's KV rows were held at,
    ``want_bits`` the configuration's."""
    return {"max_logit_gap": {"value": reading.get("max_gap", float("inf")),
                              "limit": chk["max_logit_gap"]},
            "tokens_compared": {"value": reading.get("tokens", 0),
                                "limit": chk["min_tokens"]},
            "kv_cache_bits": {"value": kv_bits, "limit": want_bits}}


def judge(checks: dict, events=()) -> bool:
    """``correct``: the served tokens lie within the limit of the
    reference's best, enough of them were compared, the KV rows were held
    at the configuration's precision, and the engine reported no fault."""
    return (checks["max_logit_gap"]["value"]
            <= checks["max_logit_gap"]["limit"]
            and checks["tokens_compared"]["value"]
            >= checks["tokens_compared"]["limit"]
            and checks["kv_cache_bits"]["value"]
            >= checks["kv_cache_bits"]["limit"]
            and not events)


async def drive(eng, cell: Cell, seed: int, seconds: float, trace: bool,
                tpu: bool, counter: CompileCounter, log) -> tuple:
    """Serve ``eng``, warm up, run the mix for ``seconds`` and close."""
    from repro.serving.server import ServingServer

    server = ServingServer(eng, host="127.0.0.1", port=0)
    await server.start()
    load = C.Load(server.host, server.port)
    try:
        win, ticks = await _window(server, load, eng, cell, seed, seconds,
                                   trace, tpu, counter)
    finally:
        await load.close()
        await server.drain_and_stop(timeout_s=10.0)
    log(f"window {seconds:.1f}s: {len(load.started)} streams started, "
        f"{win.compiles} compiles inside")
    return load, win, (ticks.records if ticks else None)


async def _window(server, load, eng, cell, seed, seconds, trace, tpu,
                  counter):
    while not server.ready:
        await asyncio.sleep(0.02)
    win = Window(t_ready=time.perf_counter(), kv_bits=cache_bits(eng))
    faults = engine_faults(await server.driver.stats(), tpu=tpu)
    if faults:
        raise SetupError(f"before the window: {faults}")
    ticks = Ticks(eng) if trace else None
    vocab = cell.config["vocab_size"]
    max_len = cell.spec["max_len"]
    if cell.mix["loop"] == "closed":
        plans = T.closed_plan(cell.mix, clients=cell.spec["clients"],
                              seed=seed, vocab=vocab, max_len=max_len)
        load.closed(plans)
        warm = len(plans) if cell.mix.get("warm_start") else 0
        t_lim = time.perf_counter() + 300.0
        # the window opens once every warm-start stream has its first token
        while sum(1 for s in load.started if s.req.warm and s.times) < warm:
            if time.perf_counter() > t_lim:
                raise SetupError("warm-start streams did not start")
            await asyncio.sleep(0.005)
        win.t_open = time.perf_counter()
    else:
        reqs = T.open_plan(cell.mix, rate=cell.spec["rate"], seconds=seconds,
                           seed=seed, vocab=vocab, max_len=max_len)
        t0 = time.perf_counter()
        load.open(reqs, t0)
        win.t_open = t0 + float(cell.mix.get("ramp_s", 0.0))
        await asyncio.sleep(max(0.0, win.t_open - time.perf_counter()))
    n0 = counter.n
    win.setup_compiles = {k.rsplit("/", 1)[1]: v
                          for k, v in counter.by_event.items()}
    win.t_close = win.t_open + seconds
    if trace:
        import jax

        win.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(win.trace_dir)
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        a = time.perf_counter()
        await asyncio.sleep(max(0.0, min(TRACE_S, win.t_close - a)))
        win.trace_host = (a, time.perf_counter())
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    await asyncio.sleep(max(0.0, win.t_close - time.perf_counter()))
    win.compiles = counter.n - n0
    if load.dry:
        raise SetupError(f"{load.dry} clients ran out of requests before "
                         f"the window closed")
    await load.close()
    win.events = engine_faults(await server.driver.stats(), tpu=tpu)
    win.kv_bits = min(win.kv_bits, cache_bits(eng))
    return win, ticks


def sample_streams(streams: list, k: int, seed: int) -> list:
    """``k`` finished streams drawn from the seed, the longest among them."""
    done = [s for s in streams if s.ok]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].req.prompt) + len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    pick = T.rng_for(seed, 3).permutation(rest)[: max(0, k - 1)]
    return [done[longest]] + [done[i] for i in sorted(pick)]


@dataclasses.dataclass
class Ctx:
    """What a metric reader can read."""
    cell: Cell
    seconds: float
    setup_s: float
    streams: list
    window: Window
    lateness: list
    ticks: list | None = None  # tick records inside the traced span
    trace: devtrace.Trace | None = None
    trace_span: tuple | None = None  # traced span on the trace's clock (ns)
    peaks: dict | None = None

    def in_window(self, t: float) -> bool:
        return self.window.t_open <= t < self.window.t_close

    def trace_s(self) -> float:
        a, b = self.trace_span
        return (b - a) * 1e-9

    def busy_s(self) -> float:
        a, b = self.trace_span
        ops = self.trace.ops
        return sum(devtrace.busy_ns(o, a, b) for o in ops) * 1e-9 / len(ops)

    def kernel_s(self, family: str) -> float:
        pats = _json(os.path.join(BENCH, "kernels.json"))[family]
        a, b = self.trace_span
        return sum(devtrace.kernel_seconds(o, a, b, pats)
                   for o in self.trace.ops) / len(self.trace.ops)


def _traced(ctx: Ctx, ticks: list, win: Window) -> None:
    tr = devtrace.load(devtrace.find_xplane(win.trace_dir))
    shutil.rmtree(win.trace_dir, ignore_errors=True)
    spans = [s for s in tr.spans if s[0] == "bench.window"]
    if not spans or not tr.ops:
        raise SetupError("the trace holds no window span or no device plane")
    ctx.trace = tr
    ctx.trace_span = (spans[0][1], spans[0][2])
    a, b = win.trace_host
    ctx.ticks = [t for t in ticks if a <= t["t"] < b]


def breakdown(ctx: Ctx) -> dict:
    a, b = ctx.trace_span
    tot = {}
    for ops in ctx.trace.ops:
        for k, v in devtrace.op_totals(ops, a, b).items():
            tot[k] = tot.get(k, 0.0) + v
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
    gaps = devtrace.idle_gaps(ctx.trace.ops[0], a, b)[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[devtrace.label_gap(ctx.trace.spans, s, e),
                           (e - s) * 1e-9] for s, e in gaps]}


def prepare(cell: Cell, seed: int, *, smoke: bool = False,
            kv: str | None = None, marks: dict | None = None):
    """Set-up up to the served engine: compile cache (every program, so a
    warm run compiles nothing), pinned autotune store, the program's
    configuration checked against the file, weights from the seed.
    ``kv`` serves another KV-cache dtype (the control only); ``marks``
    gets the clock at the end of each part.
    Returns ``(engine, config dict, compile counter)``."""
    import jax

    from repro.core import bitlinear
    from repro.kernels import autotune
    from repro.launch import server as launcher
    from repro.launch.compile_cache import use_compile_cache

    if not smoke:  # the tests' tiny runs leave the shared cache alone
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    store = os.path.join(ROOT, "autotune", f"{autotune.device_key()}.json")
    if os.path.exists(store):
        raise SetupError(f"an autotune table exists at {store}; runs must "
                         f"not depend on timing files")
    autotune.set_cache_path(store)
    counter = CompileCounter()
    args = server_args(cell, seed, smoke, kv)
    cfg = launcher.build_config(args)
    c = dict(cell.config)
    if smoke:
        c.update(smoke_config(cfg))
    check_config(cfg, {**c, "kv_cache_dtype": kv or c["kv_cache_dtype"]})
    marks = {} if marks is None else marks
    marks["config"] = time.perf_counter()
    packed = W.build_served(c, seed, bitlinear.pack_params)
    marks["weights"] = time.perf_counter()
    eng = launcher.build_backend(args, params=packed)
    marks["backend"] = time.perf_counter()
    return eng, c, counter


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, smoke: bool = False, tpu: bool = True,
             variants=("ref",), fault=None, kv=None, log=None,
             t_devices: float | None = None) -> dict:
    """One run; returns the result's fields (``correct`` etc.) plus
    ``checks`` and, for the caller's log, ``info``. ``variants`` are the
    reference's passes (``reference.run``); ``kv`` serves another KV-cache
    dtype than the configuration's and ``fault`` breaks the engine, both
    for the control and the tests only. ``t_devices``, when given, is the
    clock once JAX had found its devices, and splits the first part of
    set-up in two."""
    import jax

    log = log or (lambda m: print(f"[bench] {m}", file=sys.stderr,
                                  flush=True))
    marks = {} if t_devices is None else {"devices": t_devices}
    marks["entry"] = time.perf_counter()
    eng, c, counter = prepare(cell, seed, smoke=smoke, kv=kv, marks=marks)
    if fault is not None:
        fault(eng)
    load, win, ticks = asyncio.run(
        drive(eng, cell, seed, seconds, trace, tpu, counter, log))
    setup_s = win.t_open - t_start
    marks.update(warmup=win.t_ready, ramp=win.t_open)
    ends = list(marks.items())
    setup_parts = {k: b - a for (_, a), (k, b) in
                   zip([("start", t_start)] + ends, ends)}
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    del eng
    gc.collect()
    if win.compiles:
        raise SetupError(f"{win.compiles} compiles inside the window")
    ctx = Ctx(cell, seconds, setup_s, load.streams, win, load.lateness)
    if trace:
        _traced(ctx, ticks, win)
        ctx.peaks = work.peaks(dev.device_kind)
        device["busy_s"] = ctx.busy_s()
        device["window_s"] = ctx.trace_s()

    # correctness: the reference over a sample of the finished streams
    chk = cell.spec["check"]
    sample = sample_streams(load.streams, chk["requests"], seed)
    t0 = time.perf_counter()
    ref = reference.run(c, seed, [(s.req.prompt, s.tokens) for s in sample],
                        seq_len=cell.spec["max_len"],
                        variants=variants) if sample else {}
    ref_s = time.perf_counter() - t0
    checks = make_checks(chk, ref.get("ref", {}), win.kv_bits,
                         KV_BITS[cell.config["kv_cache_dtype"]])
    correct = judge(checks, win.events)

    out = {}
    for m in metrics_for(cell, trace):
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = len(load.streams)
    failed = sum(1 for s in load.streams if not s.ok and not s.cut)
    res = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": out, "device": device}
    if trace:
        res["breakdown"] = breakdown(ctx)
    res["checks"] = checks
    lat = np.asarray(load.lateness) * 1e3
    ttft = [s.times[0] - s.t_sched for s in load.streams
            if s.times and ctx.in_window(s.times[0])]
    itl = [b - a for s in load.streams for a, b in zip(s.times, s.times[1:])
           if ctx.in_window(b)]
    med = lambda v: float(np.median(v) * 1e3) if v else None
    res["info"] = {
        "ttft_ms_p50": med(ttft), "ttft_samples": len(ttft),
        "itl_ms_p50": med(itl), "itl_samples": len(itl),
        "engine_faults": win.events, "reference_s": ref_s,
        "reference": ref, "sampled_requests": len(sample),
        "setup_s": setup_s, "setup_parts": setup_parts,
        "setup_compiles": win.setup_compiles,
        "streams": attempted,
        "cut_at_close": sum(1 for s in load.streams if s.cut),
        "stream_errors": [s.error for s in load.streams if s.error][:3],
        "lateness_ms_p95": float(np.percentile(lat, 95)) if len(lat) else 0.0,
        "lateness_ms_max": float(lat.max()) if len(lat) else 0.0}
    return res
