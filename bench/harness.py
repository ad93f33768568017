"""One run of one cell: set-up, the measured window, the check, the line.

``run_cell`` is the whole run; ``bench/run.py`` only parses arguments,
finds the files and refuses a machine without the chips.  Tests drive
``run_cell`` on the CPU at tiny sizes with ``require_tpu=False``.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import numpy as np

from bench import counts, loadgen, spec, stats

# published config.json keys -> the program's ModelConfig fields
HF_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "ffn_dim": "d_ff", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "n_routed_experts": "n_experts", "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "experts_per_token",
    "moe_intermediate_size": "moe_d_ff",
    "first_k_dense_replace": "first_dense_layers",
}

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def model_config(c: dict):
    """The program's ModelConfig for a configuration file: the program's
    own config of ``program.arch`` with every number of the file set (a
    published ``null``, as DeepSeek-V2-Lite's ``q_lora_rank``, is the
    program's 0: not there)."""
    from repro.configs import get_config
    from repro.quant import QuantSpec
    prog = dict(c["program"])
    arch = prog.pop("arch")
    fields = {f: 0 if c[k] is None else c[k]
              for k, f in HF_KEYS.items() if k in c}
    fields.setdefault("n_kv_heads", fields["n_heads"])
    fields.update(prog)
    q = c["quant"]
    qspec = QuantSpec(format=q["format"], bits=q["bits"],
                      group_size=q["group_size"], backend=q["backend"])
    return get_config(arch).replace(quant=qspec, **fields)


def pool_blocks(c: dict) -> int:
    """Blocks of the KV pool: the deployment's KV capacity in tokens
    (``engine.kv_pool_tokens`` of the configuration), in whole blocks."""
    e = c["engine"]
    return -(-int(e["kv_pool_tokens"]) // e["block_size"])


@dataclasses.dataclass
class Setup:
    model: object
    params: object
    engine: object
    frontend: object
    linears: List[counts.Linear]
    shape: counts.Shape
    weight_bytes: int
    num_blocks: int


def build(c: dict, seed: int) -> Setup:
    import jax
    from repro.models import Model
    from repro.serve import AsyncServeFrontend, PagedServeEngine
    from bench import weights

    cfg = model_config(c)
    model = Model(cfg)
    params = weights.draw(model, cfg.quant, seed)
    jax.block_until_ready(params)
    wbytes = weights.total_bytes(params)
    e = c["engine"]
    nb = pool_blocks(c)
    eng = PagedServeEngine(
        model, params, num_blocks=nb, block_size=e["block_size"],
        max_batch=e["max_batch"], max_seq_len=e["max_seq_len"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        paged_kernel=e["paged_kernel"], prefix_cache=e["prefix_cache"])
    fe = AsyncServeFrontend(eng, max_queue=e["max_queue"])
    return Setup(model, params, eng, fe, weights.linears(params),
                 counts.shape_of(c, weights.head(params)),
                 wbytes, nb)


def warm_up(s: Setup, c: dict) -> None:
    """Compile (or load from the cache) every program the window drives:
    the decode step at the batch, each prefill bucket, and the host-side
    ops whose shapes vary with the number of rows carried over a tick."""
    import jax
    import jax.numpy as jnp
    e = c["engine"]
    prompts = loadgen.warmup_prompts(e["prefill_buckets"], c["vocab_size"])

    async def go():
        for i, (p, n) in enumerate(prompts):
            s.frontend.submit_nowait(p, max_new_tokens=n, uid=10**9 + i)
        await s.frontend.drain()
    asyncio.run(go())
    bad = [r.uid for r in s.engine.finished if r.error]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad}")
    # the async tick gathers the rows whose input token is still on the
    # device: one small gather/scatter per row count
    b = e["max_batch"]
    inp = jnp.zeros((b, 1), jnp.int32)
    prev = jnp.zeros((b,), jnp.int32)
    for n in range(1, b + 1):
        r = np.arange(n)
        jax.block_until_ready(inp.at[r, 0].set(prev[r]))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    item: loadgen.Item
    due: float = 0.0
    sent: float = math.nan
    times: list = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    req: object = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.req is not None \
            and self.req.done and self.req.error is None


@dataclasses.dataclass
class Window:
    recs: List[Rec]                      # requests due in the window
    t0: float
    t1: float
    drain_end: float
    compiles: int
    trace: Optional[tuple] = None        # (host start, host stop, dir)
    pre: List[Rec] = dataclasses.field(default_factory=list)


def run_window(s: Setup, items, seconds: float, drain_s: float,
               trace_s: float = 0.0, uid_base: int = 0, preroll=(),
               preroll_s: float = 0.0, clock=time.perf_counter) -> Window:
    """Offer the ``preroll`` items over ``preroll_s`` seconds, then the
    window's ``items`` over ``seconds``, open-loop at their due times
    through the frontend, and drain up to ``drain_s`` past the close.
    Request ids are ``uid_base`` plus the item's index (pre-roll ids
    follow the window's)."""
    import jax
    from bench import devtrace
    fe = s.frontend
    recs = [Rec(it) for it in items]
    pre = [Rec(dataclasses.replace(it, index=len(items) + it.index))
           for it in preroll]
    compiles = []
    done_ev = {}

    def on_compile(event, duration, **kw):
        if event == COMPILE_EVENT and done_ev.get("open"):
            compiles.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    trace = {}

    async def profile(t_start):
        await asyncio.sleep(max(0.0, t_start - clock()))
        trace["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace["dir"], profiler_options=opts)
        # ties the profiler's clock to this one (see bench.devtrace)
        with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
            trace["start"] = clock()

    async def go():
        loop_task = asyncio.create_task(fe.serve_forever())
        await asyncio.sleep(0)
        t0 = clock() + preroll_s
        t1 = t0 + seconds
        prof = None
        for r in pre:
            r.due = t0 - preroll_s + r.item.due_s
        for r in recs:
            r.due = t0 + r.item.due_s
        for r in pre + recs:
            if r is (recs[0] if recs else None):
                done_ev["open"] = True
                if trace_s:
                    prof = asyncio.create_task(profile(t1 - trace_s))
            delay = r.due - clock()
            if delay > 0:
                await asyncio.sleep(delay)
            r.sent = clock()
            try:
                h = fe.submit_nowait(r.item.prompt,
                                     max_new_tokens=r.item.max_new,
                                     uid=uid_base + r.item.index)
            except Exception as ex:          # QueueFullError and the like
                r.error = type(ex).__name__
                continue
            r.req = h.request
            times = r.times
            h.request.on_token = lambda tok, req, t=times: t.append(clock())
        await asyncio.sleep(max(0.0, t1 - clock()))
        done_ev["open"] = False
        if prof is not None:
            await prof
        end = t1 + drain_s
        while clock() < end and any(
                r.req is not None and not r.req.done for r in pre + recs):
            await asyncio.sleep(0.01)
        drain_end = clock()
        if prof is not None:
            # the traced slice ends at the close; writing the trace blocks
            # the loop for seconds, so it waits until nothing is served
            trace["stop"] = t1
            jax.profiler.stop_trace()
        fe.close()
        await loop_task
        return t0, t1, drain_end

    try:
        t0, t1, drain_end = asyncio.run(go())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    for r in pre + recs:
        if r.error is None and r.req is not None and r.req.error:
            r.error = r.req.error
        elif r.error is None and (r.req is None or not r.req.done):
            r.error = "unfinished"
    tr = (trace["start"], trace["stop"], trace["dir"]) if trace else None
    return Window(recs, t0, t1, drain_end, len(compiles), tr, pre)


def end_to_end(w: Window) -> dict:
    """The cell's end-to-end metrics from the host clock: output tokens
    emitted in the window over its seconds; time to first token of the
    window's requests (mean, median and p95); gaps between tokens emitted
    in it (p99)."""
    seconds = w.t1 - w.t0
    ttft, gaps, emitted = [], [], 0
    for r in w.recs:
        if r.ok and r.times:
            ttft.append(r.times[0] - r.due)
        else:
            # missing: it waited at least until the drain gave up
            ttft.append(w.drain_end - r.due)
    # every request running in the window, the pre-roll's too
    for r in w.pre + w.recs:
        emitted += sum(1 for t in r.times if w.t0 <= t <= w.t1)
        gaps += [b - a for a, b in zip(r.times, r.times[1:])
                 if w.t0 <= b <= w.t1]
    return {
        "output_tok_per_s": emitted / seconds,
        "ttft_mean_ms": stats.mean(ttft) * 1e3,
        "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
        "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
        "itl_p99_ms": stats.percentile(gaps, 99) * 1e3,
    }


def generator_lateness(w: Window) -> dict:
    late = [r.sent - r.due for r in w.recs if not math.isnan(r.sent)]
    return {"late_p50_ms": stats.percentile(late, 50) * 1e3,
            "late_max_ms": max(late) * 1e3 if late else math.nan}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample_finished(w: Window, n: int, seed: int) -> List[Rec]:
    """``n`` finished requests drawn from the seed, the longest (by
    tokens served) always among them."""
    done = [r for r in w.pre + w.recs if r.ok and r.req.out_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.out_tokens), -r.item.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_rows(tr: dict, recs: List[Rec]):
    """Token rows for the reference: each prompt with its served tokens,
    right-padded to the mix's longest request and to ``reference_sample``
    rows (one shape for every run, so its programs come from the cache),
    the next-token targets, and per row the positions whose next token
    was served."""
    t_max = -(-(tr["prompt_len"]["max"] + tr["output_len"]["max"]) // 128) \
        * 128
    toks = np.zeros((tr["reference_sample"], t_max), np.int32)
    tgts = np.zeros_like(toks)
    spans = []
    for i, r in enumerate(recs):
        seq = np.concatenate([r.item.prompt,
                              np.asarray(r.req.out_tokens, np.int32)])
        toks[i, :len(seq)] = seq
        tgts[i, :len(seq) - 1] = seq[1:]
        spans.append((len(r.item.prompt) - 1, len(seq) - 1))
    return toks, tgts, spans


def served_gaps(best, at, spans) -> np.ndarray:
    """For every served token: how far its reference logit lies below the
    reference's best at that position."""
    best, at = np.asarray(best), np.asarray(at)
    return np.concatenate([best[i, a:b] - at[i, a:b]
                           for i, (a, b) in enumerate(spans)])


def check(c: dict, tr: dict, s: Setup, recs: List[Rec], control=False,
          bench_dir: str = spec.BENCH_DIR) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over the sampled requests.

    ``control=True`` judges the control the same way: the reference with
    float8 linears in the program's place, at every served position the
    token it puts first."""
    from bench import weights
    from bench.reference.common import logit_stats
    ref = spec.reference(c["reference"], bench_dir)
    toks, tgts, spans = reference_rows(tr, recs)
    w = weights.plain(s.params)
    if control:
        _, _, tgts = logit_stats(ref, w, c, toks, tgts, "fp8")
    best, at, _ = logit_stats(ref, w, c, toks, tgts)
    gaps = served_gaps(best, at, spans)
    return {"max_logit_gap": float(gaps.max()) if gaps.size else math.inf,
            "tokens_compared": int(gaps.size)}


def judge(got: dict, short: int, c: dict, tr: dict):
    """``(correct, compared)``: each number the check compares beside its
    limit, and whether every one keeps to it."""
    compared = {
        "max_logit_gap": [got["max_logit_gap"], c["check"]["max_logit_gap"]],
        "short_outputs": [short, 0],
        "tokens_compared_min": [got["tokens_compared"],
                                tr["reference_min_tokens"]],
    }
    correct = (got["max_logit_gap"] <= compared["max_logit_gap"][1]
               and short == 0
               and got["tokens_compared"] >= tr["reference_min_tokens"])
    return bool(correct), compared


def short_outputs(recs: List[Rec]) -> int:
    """Finished requests that served other than the tokens they asked for."""
    return sum(1 for r in recs
               if r.ok and len(r.req.out_tokens) != r.item.max_new)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def device_info() -> dict:
    """The device as JAX reports it, with the fullest chip's peak bytes."""
    import jax
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def run_cell(bench: dict, cell: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             bench_dir: str = spec.BENCH_DIR) -> dict:
    """The whole run; returns the result line's object."""
    import jax
    from bench import devtrace, layers

    wl = spec.workload(bench, cell)
    c = spec.config(bench, wl["config"], bench_dir)
    tr = spec.traffic(wl["traffic"], bench_dir)
    dev = jax.devices()[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise SystemExit(f"no TPU: jax.devices()[0] is {dev.platform!r}")
        if len(jax.devices()) < wl["chips"]:
            raise SystemExit(f"{len(jax.devices())} chips, the cell asks "
                             f"for {wl['chips']}")
    readers = [(m, spec.reader(m, bench_dir))
               for m in spec.per_layer(bench, cell)] if trace else []

    s = build(c, seed)
    warm_up(s, c)
    items = loadgen.schedule(tr, seed, seconds, c["vocab_size"])
    pre = loadgen.schedule(tr, seed, tr["preroll_s"], c["vocab_size"],
                           part=1) if tr["preroll_s"] else []
    tracer = None
    if trace:
        from repro import obs
        tracer = obs.Tracer(capacity=1 << 22, profiler_bridge=True)
        s.engine.attach_tracer(tracer)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    w = run_window(s, items, seconds, tr["drain_s"],
                   tr["trace_s"] if trace else 0.0, preroll=pre,
                   preroll_s=tr["preroll_s"])
    info = device_info()
    log(f"window: {len(w.recs)} requests due over {seconds:g} s at "
        f"{tr['arrivals']['rate_per_s']:g}/s after {len(w.pre)} in "
        f"{tr['preroll_s']:g} s of pre-roll; drained "
        f"{w.drain_end - w.t1:.1f} s; programs built inside it: "
        f"{w.compiles}; generator lateness {generator_lateness(w)}; "
        f"pool {s.num_blocks} blocks; weights {s.weight_bytes} B")

    failed = sum(1 for r in w.recs if not r.ok)
    short = short_outputs(w.recs)
    metrics, breakdown, dev_extra = {}, None, {}
    if trace:
        t_start_tr, t_stop_tr, tdir = w.trace
        dtrace = devtrace.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = layers.Context(engine=c["engine"], linears=s.linears,
                             shape=s.shape, window=(w.t0, w.t1),
                             trace_window=(t_start_tr, t_stop_tr),
                             device=dtrace, tracer=tracer, recs=w.recs,
                             kind=info["kind"], drain_end=w.drain_end)
        for entry, mod in readers:
            v = mod.read(ctx)
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
        dev_extra = {"busy_s": ctx.busy_s(), "window_s": ctx.trace_seconds}
        breakdown = ctx.breakdown()
    else:
        e2e = end_to_end(w)
        log(f"host-clock readings: {json.dumps(e2e)}")
        for m in spec.end_to_end(bench, cell):
            name = m["name"]
            v = setup_s if name == "setup_s" else e2e[name]
            metrics[name] = {"value": v, "unit": m["unit"]}

    # the check runs once the window's state is gone; only the weights
    # it compares against stay
    sample = sample_finished(w, tr["reference_sample"], seed)
    w.recs = None
    s.engine.cache = None
    s.frontend = None
    s.engine = None
    gc.collect()
    t_check = time.perf_counter()
    got = check(c, tr, s, sample, bench_dir=bench_dir) if sample else \
        {"max_logit_gap": math.inf, "tokens_compared": 0}
    correct, compared = judge(got, short, c, tr)
    log(f"check took {time.perf_counter() - t_check:.1f} s; run "
        f"{time.perf_counter() - t_start:.1f} s")
    for k, (v, lim) in compared.items():
        log(f"check {k}: {v} (limit {lim})")
    out = {"correct": correct, "attempted": len(items),
           "failed": failed, "metrics": metrics,
           "device": {**info, **dev_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in compared.items()}
    return out


def emit(out: dict) -> None:
    print(json.dumps(out), flush=True)
