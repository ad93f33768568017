"""Serving engines: paged continuous batching (primary) and the
fixed-slot contiguous engine (reference / fallback).

``PagedServeEngine`` is the production shape: KV lives in a shared block
pool (``serve/paging.py``), a scheduler (``serve/scheduler.py``) admits
FCFS by free-block budget, prefill runs in bucket-sized chunks written
straight into the pool, decode and prefill interleave every tick, the
pool preempts-by-recompute when it runs dry, and per-token streaming
callbacks plus ``serve/metrics.py`` telemetry come for free.  Capacity
is bounded by *actual tokens held*, not worst-case reservations — the
whole point of paging.

The engine has two tick modes sharing one scheduler and one cache:

  * ``step()`` — synchronous: dispatch, block on the device, sample on
    the host.  The reference semantics.
  * ``step_async()`` — double-buffered: plan against *projected* state,
    dispatch step N with sampling fused on-device
    (:meth:`repro.models.Model.decode_and_sample`, so only token ids
    ever cross the host boundary), then sync and emit step N-1's
    tokens.  The host runs one step behind the device; the device queue
    never drains while there is decode work.  Token-for-token (and
    schedule-for-schedule) identical to ``step()`` under fixed seeds —
    see ``docs/serving.md`` ("Async host loop") for the invariant
    argument.

``ServeEngine`` keeps the contiguous fixed-slot design: every request
reserves a full ``cache_len`` row.  It is the equivalence oracle for the
paged engine (greedy outputs must match token-for-token) and still
serves models the paged cache doesn't cover (SSM/hybrid, enc-dec,
sliding-window).

Both work with dense or plane-bundle-quantized params transparently —
the config's :class:`~repro.quant.QuantSpec` sets the backend
*preference* and the registry's capability negotiation picks the
execution path per weight (kind-aware: ternary bundles route to the
dedicated kernel) — the deployment shape of the paper's engine:
weight-only-quantized LLM decode.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from contextlib import nullcontext as _null_scope
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.ops import live_pages
from repro.models import Model
from repro.models.model import sample_tokens
from repro.obs import trace as obs_trace
from repro.obs.trace import req_track
from repro.serve.metrics import ServeMetrics
from repro.serve.paging import BlockPool, PrefixCache, set_block_tables
from repro.serve.scheduler import Scheduler


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # int32 [prompt_len]
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 => greedy
    top_k: int = 0                # 0 => no truncation (temperature > 0 only)
    seed: Optional[int] = None    # per-request sampling seed (None: engine
                                  # seed folded with uid — still deterministic)
    deadline_s: Optional[float] = None   # absolute, on the engine's clock
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    on_token: Optional[Callable] = None   # streaming: fn(token, request)
    error: Optional[str] = None           # "too_long" | "oom" | "callback"
                                          # | "deadline" | "cancelled" | None
    # the wait for the first token, traced as PHASES: the index of the
    # phase in progress (len(PHASES) once answered, or when a tracer
    # attached mid-wait) and its start on the tracer's clock
    phase: int = 0
    t_phase: float = 0.0


#: a request's wait phases, each closed by the next boundary: first
#: admission, dispatch of its first prefill chunk, its first token
PHASES = ("queue", "prefill_wait", "prefill")


def _emit(req: Request, tok: int) -> None:
    req.out_tokens.append(int(tok))
    cb = req.on_token
    if cb is None:
        return
    try:
        cb(int(tok), req)
    except Exception:
        # a broken streaming consumer must fail ITS request, not wedge
        # the tick (and every other in-flight stream) — the engine
        # retires the request with error="callback" when it sees this
        req.error = "callback"
        req.on_token = None


def request_key(req: Request, index: int, engine_seed: int):
    """The PRNG key for a request's ``index``-th sampled token.

    Derivation is a pure function of (seed-or-uid, index): explicit
    ``req.seed`` wins, otherwise the engine seed folded with the uid, so
    distinct requests never share a stream.  ``index`` counts tokens
    sampled so far — preempt-by-recompute replays the same indices, so
    a resumed request keeps drawing the same tokens, and the sync and
    async samplers (which both receive this key as data) agree bit for
    bit."""
    if req.seed is not None:
        base = jax.random.PRNGKey(req.seed)
    else:
        base = jax.random.fold_in(jax.random.PRNGKey(engine_seed), req.uid)
    return jax.random.fold_in(base, index)


def _sample_host(req: Request, logits_row: np.ndarray,
                 engine_seed: int) -> int:
    """Synchronous host-side sampler.  Greedy stays a plain ``np.argmax``
    (bit-identical to the device's ``jnp.argmax``, ties to the lowest
    index); temperature/top-k route through the SAME
    :func:`~repro.models.model.sample_tokens` the async fused path jits,
    under the same :func:`request_key` — that identity is what the
    sync==async equivalence tests lean on."""
    if req.temperature <= 0:
        return int(np.argmax(logits_row))
    key = request_key(req, len(req.out_tokens), engine_seed)
    tok = sample_tokens(jnp.asarray(logits_row)[None],
                        jnp.asarray(key, jnp.uint32)[None],
                        jnp.full((1,), req.temperature, jnp.float32),
                        jnp.full((1,), req.top_k, jnp.int32))
    return int(tok[0])


def _pretune(model: Model, params, batch_sizes, verbose: bool = True):
    """Warm the repro.tune cache for every quantized GEMM a serving
    engine will launch (decode = active-row batches, prefill = bucket
    rows) so the first ticks hit tuned configs instead of the heuristic.
    No-op for dense params or non-Pallas backends."""
    from repro import tune as tune_mod
    from repro.core import lut_gemm as core_lg
    from repro.quant.backends import kernel_for
    kernel = kernel_for(model.cfg.backend_preference)
    if kernel is None or not tune_mod.collect_bcq_specs(params):
        return
    # interpret mode (CPU smoke): small reps + truncated space so
    # pretune stays a warm-up, not a benchmark run
    extra = (dict(reps=2, warmup=1, max_candidates=8)
             if core_lg.interpret_mode() else {})
    tune_mod.pretune_params(params, kernels=(kernel,),
                            batch_sizes=sorted(set(batch_sizes)),
                            dtype=jnp.dtype(model.cfg.dtype),
                            verbose=verbose, **extra)


def supports_paging(cfg) -> bool:
    """Whether a config can serve through the paged engine: attention-only
    decoder, no sliding window (ring caches are already fixed-size), no
    encoder-decoder cross-KV (a fixed per-row reservation)."""
    return (not cfg.is_encdec and not cfg.sliding_window
            and all(cfg.layer_kind(i) == "attn"
                    for i in range(cfg.n_layers)))


def step_programs(model: Model):
    """The engine's three device programs, each returning the step's
    counters last: ``decode_step`` -> (logits, cache, counters),
    ``decode_and_sample`` -> (tokens, cache, counters), ``prefill_chunk``
    -> (logits, cache, counters).  A model with routed experts counts on
    the device (``Model``'s ``counters=True``: ``experts_touched``,
    ``expert_load_max``); a dense model's counters are ``{}`` and its
    programs are the plain ones.  The programs keep the methods' names,
    which name the jitted programs the benchmark's trace readers match."""
    counted = bool(model.cfg.n_experts)

    def counting(method):
        @functools.wraps(method)
        def program(*args):
            if counted:
                return method(*args, counters=True)
            return (*method(*args), {})
        return program
    return (counting(model.decode_step), counting(model.decode_and_sample),
            counting(model.prefill_chunk))


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unsynced async step: the device-resident
    sampled token vector plus the host bookkeeping needed to emit it
    next tick."""
    tokens: object                 # device int32 [max_batch]
    emits: list                    # [(SeqState, row)] in sampling order
    row_of: dict                   # uid -> row, for next tick's decode input
    uids: list                     # the emits' uids, for the trace
    t_dispatch: float              # engine-clock time of dispatch
    tick: int


# ---------------------------------------------------------------------------
# paged engine
# ---------------------------------------------------------------------------


class PagedServeEngine:
    """Continuous batching over a paged KV cache.

    ``num_blocks`` x ``block_size`` KV slots are shared by up to
    ``max_batch`` concurrent sequences; each sequence holds only the
    blocks its tokens actually occupy, so total admitted context can
    exceed ``max_batch`` worst-case reservations by the pool ratio.

    ``paged_kernel`` ("auto" | "fused" | "gather", default: the model
    config's setting) picks the paged attention paths: the fused Pallas
    kernels read live pool blocks directly through the block table —
    decode for float, int8-KV (per-slot scale rows ride the same DMA)
    and MLA latent pools, chunked prefill for float and int8-KV pools —
    while "gather" materializes the contiguous ``paged_view`` per layer
    (the reference path).  The paths are resolved PER VARIANT:
    ``self.decode_path`` and ``self.prefill_path`` can differ (MLA
    decodes fused but prefills gathered, for the decompressing
    ``kv_map_fn``), and both paths' analytic KV traffic is tracked per
    step in ``metrics`` (``kv_bytes_per_token_{fused,gathered}``,
    ``kv_bytes_per_prefill_token_{fused,gathered}``).

    ``prefix_cache=True`` turns on prefix caching: fully-written prompt
    blocks are indexed by their token content and later requests with
    the same block-aligned prefix ADOPT those live blocks by reference
    instead of re-prefilling them (see ``docs/serving.md``).  Adopted
    blocks are shared and immutable — writes always land in privately
    owned blocks (copy-on-write by recompute) — so greedy outputs are
    token-for-token identical with the cache on or off.  Off by
    default: a warm cache deliberately keeps pool blocks occupied after
    their sequences retire, which changes drain-time occupancy.

    ``mesh`` (a ``("data", "model")`` jax Mesh, see
    ``launch.mesh.make_mesh_for``) serves the same engine TP/DP-sharded:
    params and KV-pool leaves are ``device_put`` through
    ``parallel.sharding.build_shardings`` (pool KV shards over
    ``kv_heads`` -> model, falling back to ``head_dim`` when the head
    count doesn't divide), block tables stay replicated host state,
    ``decode_step`` / ``prefill_chunk`` are jitted with explicit in/out
    shardings (batch rows over ``data`` when ``max_batch`` divides), and
    the fused kernel launches per model-shard through ``shard_map``.
    Scheduling, metrics and streaming are unchanged — the mesh is
    invisible above the decode step.

    ``tracer`` (an :class:`repro.obs.Tracer`, or ``attach_tracer`` after
    construction) records an event-level trace of every tick — spans for
    admission, prefix lookup, prefill chunks, decode dispatch, device
    sync and sampling on engine-phase tracks (the batched ones name
    their requests in ``uids``), every program build on
    ``engine/compile``, plus a per-request track from submit to retire
    whose ``queue`` / ``prefill_wait`` / ``prefill`` spans tile the wait
    for the first token — exportable as Chrome trace-event JSON via
    ``repro.obs.save_chrome`` (see ``docs/observability.md``).  Off by
    default; the hooks run against a no-op ``NullTracer``.  Under
    ``step_async`` the overlap is directly visible: tick N's
    ``decode_dispatch`` span precedes tick N-1's ``device_sync`` span
    inside the same ``tick`` span.
    """

    def __init__(self, model: Model, params, *, num_blocks: int = 64,
                 block_size: int = 16, max_batch: int = 8,
                 max_seq_len: int = 0, prefill_buckets=(32, 128, 512),
                 rng_seed: int = 0, pretune: bool = False,
                 paged_kernel: Optional[str] = None,
                 prefix_cache: bool = False,
                 mesh=None, shard_rules: Optional[dict] = None,
                 clock=time.perf_counter, tracer=None):
        from repro.models.attention import (kv_entry_bytes,
                                            paged_kernel_mode,
                                            paged_prefill_mode)
        if paged_kernel is not None and paged_kernel != model.cfg.paged_kernel:
            # the mode is part of the (jitted) decode graph, so it lives
            # on the config; an engine-level override rebuilds the Model
            model = Model(model.cfg.replace(paged_kernel=paged_kernel))
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.block_size = block_size
        self.buckets = sorted(prefill_buckets)
        max_seq_len = max_seq_len or model.cfg.max_seq_len
        self.max_seq_len = max_seq_len
        self.max_blocks_per_seq = -(-max_seq_len // block_size)
        self.mesh = mesh
        self._tp = 1
        self._shard_batch = False
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            self._tp = sizes.get("model", 1)
            # batch rows ride the data axis only when they divide it —
            # otherwise they stay replicated (correct, just no DP win)
            self._shard_batch = max_batch % max(sizes.get("data", 1), 1) == 0
        self.decode_path = paged_kernel_mode(
            model.cfg, block_size=block_size, pages=self.max_blocks_per_seq,
            tp=self._tp)
        self.prefill_path = paged_prefill_mode(
            model.cfg, block_size=block_size, pages=self.max_blocks_per_seq,
            tp=self._tp)
        # per-entry bytes INCLUDING the int8 pools' per-slot scale rows:
        # fused int8 decode/prefill DMA the scales alongside each block,
        # and the gathered view materializes them too, so both traffic
        # estimates must count them (see attention.kv_entry_bytes)
        self._kv_entry_bytes = kv_entry_bytes(model.cfg)
        # tracing: hooks below run unconditionally against a NullTracer
        # when tracing is off (attach_tracer swaps in a live one).  The
        # tracer goes active BEFORE pretune/jit so kernel-config
        # resolutions inside tune.dispatch land in the trace too.
        self.trace = obs_trace.NULL
        if tracer is not None:
            self.attach_tracer(tracer)
        if pretune:
            _pretune(model, params, [1, max_batch, *self.buckets])
        self.cache = model.init_paged_cache(max_batch, num_blocks,
                                            block_size,
                                            self.max_blocks_per_seq)
        self.pool = BlockPool(num_blocks, block_size)
        self.prefix = PrefixCache(self.pool) if prefix_cache else None
        self.sched = Scheduler(self.pool, rows=max_batch,
                               buckets=self.buckets,
                               max_blocks_per_seq=self.max_blocks_per_seq,
                               max_seq_len=max_seq_len,
                               prefix_cache=self.prefix,
                               tracer=self.trace)
        self.clock = clock
        self.metrics = ServeMetrics(clock)
        self.tables = np.full((max_batch, self.max_blocks_per_seq), -1,
                              np.int32)
        self.rng_seed = rng_seed
        self.rng = np.random.default_rng(rng_seed)
        self._key_cache: dict = {}          # uid -> base PRNG key
        self._inflight: Optional[_InFlight] = None
        self._row_sh = None                 # token-row sharding when meshed
        if mesh is not None:
            self._build_sharded(num_blocks, shard_rules)
        else:
            self._attn_scope = _null_scope
            dec, dec_sample, prefill = step_programs(model)
            self._decode = jax.jit(dec)
            self._decode_sample = jax.jit(dec_sample)
            self._prefill_chunk = jax.jit(prefill)
        self._sample_only = jax.jit(sample_tokens)
        self.ticks = 0
        self.steps = 0                      # device steps dispatched
        self._counters: list = []           # (step id, device counters)
        self.finished: list = []

    def _build_sharded(self, num_blocks: int, shard_rules) -> None:
        """Shard params + KV pool over the mesh and re-jit the device
        entry points with explicit in/out shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import attention as attn
        from repro.parallel import sharding as shd
        mesh, model = self.mesh, self.model
        rules = shard_rules or shd.make_rules()
        p_sh = shd.build_shardings(mesh, self.params, model.axes(), rules)
        self.params = jax.device_put(self.params, p_sh)
        cache_axes = model.paged_cache_axes(
            self.max_batch, num_blocks, self.block_size,
            self.max_blocks_per_seq)
        c_sh = shd.build_shardings(mesh, self.cache, cache_axes, rules)
        self.cache = jax.device_put(self.cache, c_sh)
        rep = NamedSharding(mesh, P())
        dax = "data" if self._shard_batch else None
        row_sh = NamedSharding(mesh, P(dax, None))
        vec_sh = NamedSharding(mesh, P(dax))
        self._row_sh = row_sh
        self._attn_scope = functools.partial(
            attn.paged_shard_scope, mesh, tp=self._tp,
            shard_batch=self._shard_batch)
        dec, dec_sample, prefill = step_programs(model)
        # logits come back replicated: the sync engine samples on the
        # host every tick, so any vocab sharding would be gathered anyway
        self._decode = jax.jit(
            dec,
            in_shardings=(p_sh, row_sh, c_sh, vec_sh),
            out_shardings=(rep, c_sh, rep))
        # fused decode+sample: keys/temperature/top_k ride the batch rows
        # exactly like tokens/pos; the sampled id vector (a few bytes)
        # comes back replicated — it IS the host boundary now
        self._decode_sample = jax.jit(
            dec_sample,
            in_shardings=(p_sh, row_sh, c_sh, vec_sh, row_sh, vec_sh,
                          vec_sh),
            out_shardings=(rep, c_sh, rep))
        self._prefill_chunk = jax.jit(
            prefill,
            in_shardings=(p_sh, {"tokens": rep}, c_sh, rep, rep),
            out_shardings=(rep, c_sh, rep))

    # ------------------------------------------------------------------
    def attach_tracer(self, tracer) -> None:
        """Attach (or detach with ``None``) an ``obs.Tracer``.  Also
        makes it the module-level *active* tracer so kernel-config
        resolutions in ``tune.dispatch`` and program builds — which
        cannot be handed an instance — record into the same ring, and
        writes into it the builds the process logged before.  Requests
        already waiting for their first token get no phase spans: their
        phases started on another clock."""
        self.trace = tracer if tracer is not None else obs_trace.NULL
        obs_trace.set_active(tracer)
        obs_trace.replay_builds(self.trace)
        if hasattr(self, "sched"):
            self.sched.trace = self.trace
            inflight = self._inflight.emits if self._inflight else ()
            for req in [*self.sched.waiting,
                        *(s.req for s in self.sched.running),
                        *(s.req for s, _ in inflight)]:
                req.phase = len(PHASES)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.metrics.on_submit(req.uid)
        req.phase, req.t_phase = 0, self.trace.now_us()
        self.trace.instant("submit", track=req_track(req.uid), cat="request",
                           uid=req.uid, prompt_len=len(req.prompt),
                           max_new=req.max_new_tokens)
        self.sched.submit(req)

    def _sync_tables(self) -> None:
        self.tables.fill(-1)
        for seq in self.sched.running:
            self.tables[seq.row, :len(seq.table)] = seq.table

    def _finalize_detached(self, req: Request) -> None:
        """Complete/fail a request whose blocks and row are already
        released (normal retire, async retire-at-dispatch, or a
        cancelled waiting request)."""
        req.done = True
        self.finished.append(req)
        self._key_cache.pop(req.uid, None)
        if req.error:                     # e.g. "oom": truncated output
            self.metrics.on_fail(req.uid, req.error)
            self.trace.instant("fail", track=req_track(req.uid),
                               cat="request", uid=req.uid,
                               error=req.error)
        else:
            self.metrics.on_complete(req.uid)
            self.trace.instant("complete", track=req_track(req.uid),
                               cat="request", uid=req.uid,
                               tokens=len(req.out_tokens))

    def _retire(self, seq) -> None:
        self.sched.finish(seq)
        self._finalize_detached(seq.req)

    def _fail_detached(self, req: Request, error: str) -> None:
        req.error = req.error or error
        self._finalize_detached(req)

    # ------------------------------------------------------------------
    def cancel(self, req: Request, error: str = "cancelled") -> bool:
        """Cancel a request wherever it currently lives — waiting queue,
        running (frees its pool blocks and batch row; prefix-cache
        references survive by design, the cache holds its own refs), or
        sampled-but-unsynced in the async in-flight step (its token is
        dropped at emission).  Returns False if it already finished."""
        if req.done:
            return False
        if req in self.sched.waiting:
            self.sched.waiting.remove(req)
            self._fail_detached(req, error)
            return True
        for seq in self.sched.running:
            if seq.req is req:
                req.error = error
                self._retire(seq)
                return True
        # neither waiting nor running nor done: an async retiring seq
        # whose final tokens are still in flight — blocks/row are
        # already free, so only the bookkeeping remains
        self._fail_detached(req, error)
        return True

    def _check_deadlines(self) -> None:
        """Expire requests whose deadline passed, waiting or running.
        Runs at the top of every tick (both modes) on the engine clock;
        an expired running request frees its blocks immediately."""
        now = self.clock()
        expired_w = [r for r in self.sched.waiting
                     if r.deadline_s is not None and now >= r.deadline_s]
        for req in expired_w:
            self.sched.waiting.remove(req)
            self.trace.instant("deadline", track=req_track(req.uid),
                               cat="request", uid=req.uid)
            self._fail_detached(req, "deadline")
        for seq in [s for s in self.sched.running
                    if s.req.deadline_s is not None
                    and now >= s.req.deadline_s]:
            seq.req.error = "deadline"
            self.trace.instant("deadline", track=req_track(seq.uid),
                               cat="request", uid=seq.uid)
            self._retire(seq)

    # ------------------------------------------------------------------
    def _kv_pages(self, tables: np.ndarray, q_pos: np.ndarray) -> tuple:
        """(live, grid) page-steps of one paged-attention call: the pages
        the fused kernels read (:func:`live_pages` over the call's rows)
        and ``rows x max_blocks_per_seq``, the steps of a grid that walks
        every table entry.  The ``kv_pages_live`` / ``kv_pages_grid``
        arguments of the ``decode_dispatch`` and ``prefill_chunk``
        spans."""
        live = int(live_pages(tables, q_pos, self.block_size, xp=np).sum())
        return live, len(tables) * self.max_blocks_per_seq

    def _kv_bytes(self, live: int, grid: int) -> tuple:
        """Analytic KV traffic of both paged-attention paths for one call
        (bytes), from its :meth:`_kv_pages` counts.

        fused: every live pool block (the pages each row can see) is read
        exactly once per layer; the kernels' page loops stop at each
        row's live bound.  gathered: ``paged_view`` reads every table
        entry of every row (unallocated entries still fetch the trash
        block), writes the contiguous view, and ``decode_attend`` /
        ``blockwise_attention`` read it back — 3 view-sized copies per
        layer however few blocks are live.  A traffic model, not a
        measurement; benchmarks report it per token."""
        per_layer = self.block_size * self._kv_entry_bytes
        layers = self.model.cfg.n_layers
        return (live * per_layer * layers, 3 * grid * per_layer * layers)

    def _request_key(self, req: Request, index: int):
        """Memoized :func:`request_key` (the base key is two fold-ins
        that would otherwise re-run per token on the host hot path)."""
        base = self._key_cache.get(req.uid)
        if base is None:
            if req.seed is not None:
                base = jax.random.PRNGKey(req.seed)
            else:
                base = jax.random.fold_in(
                    jax.random.PRNGKey(self.rng_seed), req.uid)
            self._key_cache[req.uid] = base
        return jax.random.fold_in(base, index)

    def _close_phase(self, req: Request, now: float) -> None:
        """End ``req``'s phase in progress at ``now``; the next starts."""
        self.trace.phase(PHASES[req.phase], req.uid, req.t_phase, now)
        req.phase += 1
        req.t_phase = now

    def _emit_token(self, seq, tok: int) -> None:
        if seq.req.phase == 2:           # its first token ends "prefill"
            self._close_phase(seq.req, self.trace.now_us())
        _emit(seq.req, tok)
        self.metrics.on_token(seq.req.uid)
        self.trace.instant(
            "first_token" if len(seq.req.out_tokens) == 1 else "token",
            track=req_track(seq.req.uid), cat="request", uid=seq.req.uid,
            pos=seq.kv_len)
        if seq.req.error == "callback":
            # the raising consumer poisoned only itself: retire this
            # request failed and keep every other stream ticking
            self._retire(seq)
            return
        # retire at the TOKEN bound, not the block-rounded capacity:
        # when max_seq_len is not a multiple of block_size the last
        # block has slack that must never be decoded into (positions
        # >= max_seq_len overrun learned-position tables)
        if len(seq.req.out_tokens) >= seq.req.max_new_tokens \
                or seq.kv_len + 1 >= self.max_seq_len:
            self._retire(seq)

    # ------------------------------------------------------------------
    def _plan_and_apply(self):
        """Shared tick head: deadline sweep, scheduler plan, plan-event
        metrics/tracing, table sync, prefix write-safety asserts."""
        self._check_deadlines()
        with self.trace.span("admission", track="engine/admission"):
            plan = self.sched.plan_tick()
        # metrics identity: a sequence preempted in the same tick it was
        # admitted must appear in NEITHER list (the scheduler drops such
        # net no-op victims from plan.admitted) — otherwise on_admit /
        # on_preempt would fire for a seq that never held KV
        assert {s.uid for s in plan.admitted}.isdisjoint(
            {s.uid for s in plan.preempted}), \
            "scheduler emitted admit+preempt for one seq in one tick"
        for req in plan.rejected:
            self.metrics.on_reject(req.uid)
            self.trace.instant("reject", track=req_track(req.uid),
                               cat="request", uid=req.uid, error=req.error)
            self.finished.append(req)
        now = self.trace.now_us() if plan.admitted else 0.0
        for seq in plan.admitted:
            if seq.req.phase == 0:       # first admission ends "queue"
                self._close_phase(seq.req, now)
            self.metrics.on_admit(seq.req.uid)
            self.trace.instant("admit", track=req_track(seq.req.uid),
                               cat="request", uid=seq.req.uid, row=seq.row,
                               prefill_target=seq.prefill_target,
                               prefix_hit_blocks=seq.prefix_hit,
                               free_blocks=self.pool.free_blocks)
            if self.prefix is not None:
                self.metrics.on_prefix_lookup(
                    seq.req.uid, seq.prefix_queried, seq.prefix_hit,
                    seq.shared_tokens, seq.cow_tokens)
        for seq in plan.preempted:
            self.metrics.on_preempt(seq.req.uid)
            self.trace.instant("preempted", track=req_track(seq.req.uid),
                               cat="request", uid=seq.req.uid)
        for seq in plan.failed:          # pool too dry even after preemption
            self._retire(seq)
        self._sync_tables()

        if self.prefix is not None:
            # immutability contract: every block this tick writes must be
            # privately owned by the writing sequence (shared prefix
            # blocks are read-only; CoW means they were never adopted)
            for seq in plan.decode:
                blk = seq.table[seq.kv_len // self.block_size]
                assert self.pool.writable(blk, seq.uid), \
                    f"decode would write shared block {blk}"
            if plan.prefill is not None:
                pf = plan.prefill
                lo = pf.start // self.block_size
                hi = (pf.start + pf.length - 1) // self.block_size
                for blk in pf.seq.table[lo:hi + 1]:
                    assert self.pool.writable(blk, pf.seq.uid), \
                        f"prefill would write shared block {blk}"
        return plan

    def _next_step(self) -> int:
        self.steps += 1
        return self.steps

    def _hold_counters(self, step: int, counters) -> None:
        """Keep a dispatched step's device counters until a sync finds
        them computed (only while a tracer records them)."""
        if self.trace is not obs_trace.NULL:
            self._counters.append((step, counters))

    def _emit_counters(self, block: bool = False) -> None:
        """One ``step_counters`` instant per held step whose counters the
        device has computed (all of them with ``block``): read with the
        step's outputs, never waited for."""
        keep = []
        for step, c in self._counters:
            leaves = jax.tree_util.tree_leaves(c)
            if block or all(x.is_ready() for x in leaves):
                got = {k: int(v) for k, v in jax.device_get(c).items()}
                self.trace.instant("step_counters", track="engine/sync",
                                   step=step, **got)
            else:
                keep.append((step, c))
        self._counters = keep

    def _masked_tables(self, decode) -> np.ndarray:
        tables = self.tables.copy()
        rows = {seq.row for seq in decode}
        for r in range(self.max_batch):
            if r not in rows:
                tables[r] = -1       # idle rows write to the trash block
        return tables

    def _tick_metrics(self) -> None:
        self.ticks += 1
        if self.prefix is not None:
            self.metrics.on_tick(
                self.pool.occupancy(), self.sched.active,
                logical_blocks=sum(len(s.table)
                                   for s in self.sched.running),
                physical_blocks=self.pool.used_blocks,
                prefix_cached=len(self.prefix),
                prefix_evictions=self.prefix.evictions)
        else:
            self.metrics.on_tick(self.pool.occupancy(), self.sched.active)

    # ------------------------------------------------------------------
    # synchronous tick
    # ------------------------------------------------------------------
    def step(self) -> None:
        """One synchronous tick: plan (admit / top-up / preempt), then
        run one decode batch and at most one prefill chunk, blocking on
        the device and sampling on the host.  Any async in-flight step
        is flushed first, so the two modes can interleave safely."""
        builds = obs_trace.BUILDS.total
        self.flush()
        self.trace.tick = self.ticks
        with self.trace.span("tick", track="engine/tick",
                             free_blocks=self.pool.free_blocks,
                             running=len(self.sched.running),
                             waiting=len(self.sched.waiting)):
            self._step_traced()
        self.metrics.on_builds(obs_trace.BUILDS.total - builds)

    def _step_traced(self) -> None:
        plan = self._plan_and_apply()

        if plan.decode:
            tables = self._masked_tables(plan.decode)
            tokens = np.zeros((self.max_batch, 1), np.int32)
            posv = np.zeros(self.max_batch, np.int32)
            for seq in plan.decode:
                # during decode len(tokens) == kv_len + 1, so the pending
                # input is always the last sampled token (seq.tokens would
                # rebuild the whole prompt+output list every tick)
                tokens[seq.row, 0] = seq.req.out_tokens[-1]
                posv[seq.row] = seq.kv_len
            cache = set_block_tables(self.cache, tables)
            uids = [s.uid for s in plan.decode]
            live, grid = self._kv_pages(tables, posv)
            t_disp = self.clock()
            step = self._next_step()
            with self.trace.span("decode_dispatch", track="engine/decode",
                                 rows=len(plan.decode),
                                 path=self.decode_path, uids=uids,
                                 kv_pages_live=live, kv_pages_grid=grid,
                                 step=step):
                with self._attn_scope():
                    logits, self.cache, counters = self._decode(
                        self.params, jnp.asarray(tokens), cache,
                        jnp.asarray(posv))
            self._hold_counters(step, counters)
            # the host blocks HERE, not at dispatch: np.asarray forces
            # the device computation (step_async hides exactly this span
            # behind the next tick's planning and dispatch)
            with self.trace.span("device_sync", track="engine/sync",
                                 rows=len(plan.decode), uids=uids):
                logits = np.asarray(logits)
            self.metrics.on_device_interval(t_disp, self.clock())
            fused_b, gathered_b = self._kv_bytes(live, grid)
            self.metrics.on_decode_step(len(plan.decode), fused_b,
                                        gathered_b, self.decode_path,
                                        kv_pages=(live, grid))
            with self.trace.span("sample", track="engine/sample",
                                 rows=len(plan.decode), uids=uids):
                for seq in plan.decode:
                    seq.kv_len += 1
                    tok = _sample_host(seq.req, logits[seq.row],
                                       self.rng_seed)
                    self._emit_token(seq, tok)

        if plan.prefill is not None:
            logits, seq = self._dispatch_prefill(plan.prefill)
            if seq.kv_len >= seq.prefill_target:
                with self.trace.span("sample", track="engine/sample",
                                     rows=1, uids=[seq.uid]):
                    tok = _sample_host(seq.req, np.asarray(logits)[0],
                                       self.rng_seed)
                    self._emit_token(seq, tok)

        self._emit_counters()
        self._tick_metrics()

    def _dispatch_prefill(self, pf):
        """Dispatch one prefill chunk (shared by both tick modes);
        advances ``kv_len`` and returns (device logits [1, V], seq)."""
        seq, start, clen = pf.seq, pf.start, pf.length
        if seq.req.phase == 1:           # first chunk ends "prefill_wait"
            self._close_phase(seq.req, self.trace.now_us())
        bucket = self.sched.bucket(clen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :clen] = seq.tokens[start:start + clen]
        tables = self.tables[seq.row:seq.row + 1]
        cache = set_block_tables(self.cache, tables)
        live, grid = self._kv_pages(tables, np.array([start + clen - 1]))
        step = self._next_step()
        with self.trace.span("prefill_chunk", track="engine/prefill",
                             uid=seq.uid, start=start, length=clen,
                             bucket=bucket, kv_pages_live=live,
                             kv_pages_grid=grid, step=step):
            with self._attn_scope():
                logits, self.cache, counters = self._prefill_chunk(
                    self.params, {"tokens": jnp.asarray(toks)}, cache,
                    jnp.int32(start), jnp.int32(clen - 1))
        self._hold_counters(step, counters)
        self.trace.instant("prefill_chunk", track=req_track(seq.uid),
                           cat="request", uid=seq.uid, start=start,
                           length=clen)
        fused_b, gathered_b = self._kv_bytes(live, grid)
        self.metrics.on_prefill_chunk(clen, fused_b, gathered_b,
                                      self.prefill_path,
                                      kv_pages=(live, grid))
        seq.kv_len += clen
        return logits, seq

    # ------------------------------------------------------------------
    # double-buffered async tick
    # ------------------------------------------------------------------
    def step_async(self) -> None:
        """One double-buffered tick: plan against *projected* occupancy
        (``kv_len``/``inflight`` advance at dispatch), dispatch step N
        with sampling fused on-device, THEN sync and emit step N-1's
        token ids.  The host runs one step behind the device; dispatch
        order on the device is preserved by the cache data dependency
        (tick N's compute consumes tick N-1's cache output), which is
        what makes freeing blocks at dispatch safe — the device has, in
        program order, already read them."""
        builds = obs_trace.BUILDS.total
        self.trace.tick = self.ticks
        with self.trace.span("tick", track="engine/tick", mode="async",
                             free_blocks=self.pool.free_blocks,
                             running=len(self.sched.running),
                             waiting=len(self.sched.waiting),
                             inflight=self._inflight is not None):
            self._step_async_traced()
        self.metrics.on_builds(obs_trace.BUILDS.total - builds)

    def _step_async_traced(self) -> None:
        prev, self._inflight = self._inflight, None
        plan = self._plan_and_apply()
        cur_tokens = None            # device int32 [max_batch]
        emits: list = []

        if plan.decode:
            tables = self._masked_tables(plan.decode)
            tokens = np.zeros((self.max_batch, 1), np.int32)
            posv = np.zeros(self.max_batch, np.int32)
            keys = np.zeros((self.max_batch, 2), np.uint32)
            temps = np.zeros(self.max_batch, np.float32)
            topks = np.zeros(self.max_batch, np.int32)
            dev_rows = []
            for seq in plan.decode:
                posv[seq.row] = seq.kv_len
                if prev is not None and seq.uid in prev.row_of:
                    # input token is still on the device (sampled last
                    # tick, not yet emitted); rows are stable while a
                    # seq stays running, so gather from the same row
                    assert prev.row_of[seq.uid] == seq.row
                    dev_rows.append(seq.row)
                else:
                    tokens[seq.row, 0] = seq.req.out_tokens[-1]
                if seq.req.temperature > 0:
                    idx = len(seq.req.out_tokens) + seq.inflight
                    keys[seq.row] = np.asarray(
                        self._request_key(seq.req, idx))
                temps[seq.row] = seq.req.temperature
                topks[seq.row] = seq.req.top_k
            inp = jnp.asarray(tokens)
            if dev_rows:
                r = np.asarray(dev_rows)
                inp = inp.at[r, 0].set(prev.tokens[r])
                if self._row_sh is not None:
                    # gathering from the replicated in-flight vector
                    # commits inp replicated; re-place to the declared
                    # per-row sharding before the pjit call
                    inp = jax.device_put(inp, self._row_sh)
            cache = set_block_tables(self.cache, tables)
            live, grid = self._kv_pages(tables, posv)
            t_disp = self.clock()
            step = self._next_step()
            with self.trace.span("decode_dispatch", track="engine/decode",
                                 rows=len(plan.decode), mode="async",
                                 path=self.decode_path,
                                 uids=[s.uid for s in plan.decode],
                                 kv_pages_live=live, kv_pages_grid=grid,
                                 step=step):
                with self._attn_scope():
                    cur_tokens, self.cache, counters = self._decode_sample(
                        self.params, inp, cache, jnp.asarray(posv),
                        jnp.asarray(keys), jnp.asarray(temps),
                        jnp.asarray(topks))
            self._hold_counters(step, counters)
            fused_b, gathered_b = self._kv_bytes(live, grid)
            self.metrics.on_decode_step(len(plan.decode), fused_b,
                                        gathered_b, self.decode_path,
                                        kv_pages=(live, grid))
            for seq in plan.decode:
                seq.kv_len += 1
                seq.inflight += 1
                emits.append((seq, seq.row))
                self._maybe_finish_async(seq)

        if plan.prefill is not None:
            logits, seq = self._dispatch_prefill(plan.prefill)
            if seq.kv_len >= seq.prefill_target:
                with self.trace.span("sample", track="engine/sample",
                                     rows=1, mode="async", uids=[seq.uid]):
                    idx = len(seq.req.out_tokens) + seq.inflight
                    key = (np.asarray(self._request_key(seq.req, idx))
                           if seq.req.temperature > 0
                           else np.zeros(2, np.uint32))
                    tok = self._sample_only(
                        logits,
                        jnp.asarray(key, jnp.uint32)[None],
                        jnp.full((1,), seq.req.temperature, jnp.float32),
                        jnp.full((1,), seq.req.top_k, jnp.int32))[0]
                if cur_tokens is None:
                    cur_tokens = jnp.zeros(self.max_batch, jnp.int32)
                cur_tokens = cur_tokens.at[seq.row].set(tok)
                seq.inflight += 1
                emits.append((seq, seq.row))
                self._maybe_finish_async(seq)

        # sync (and emit) the PREVIOUS tick only after this tick's work
        # is in the device queue — that ordering is the whole overlap
        self._sync_prev(prev)
        if emits:
            t_disp = t_disp if plan.decode else self.clock()
            row_of = {s.uid: row for s, row in emits}
            self._inflight = _InFlight(
                tokens=cur_tokens, emits=emits, row_of=row_of,
                uids=list(row_of), t_dispatch=t_disp, tick=self.ticks)
        self._tick_metrics()

    def _maybe_finish_async(self, seq) -> None:
        """Retire-at-dispatch: when the just-dispatched token is the
        request's last (by count — the retire decision never needs the
        token's value), release the row and blocks NOW so next tick's
        admission sees them; ``done``/completion metrics wait for the
        final emission (streaming order is preserved)."""
        if len(seq.req.out_tokens) + seq.inflight \
                >= seq.req.max_new_tokens \
                or seq.kv_len + 1 >= self.max_seq_len:
            seq.retiring = True
            self.sched.finish(seq)

    def _sync_prev(self, prev: Optional[_InFlight]) -> None:
        """Block on the previous async step and emit its tokens."""
        if prev is None:
            return
        with self.trace.span("device_sync", track="engine/sync",
                             rows=len(prev.emits), sync_tick=prev.tick,
                             uids=prev.uids):
            toks = np.asarray(prev.tokens)
        self.metrics.on_device_interval(prev.t_dispatch, self.clock())
        self._emit_counters()
        with self.trace.span("emit", track="engine/sample",
                             rows=len(prev.emits), uids=prev.uids):
            for seq, row in prev.emits:
                self._emit_async(seq, int(toks[row]))

    def _emit_async(self, seq, tok: int) -> None:
        """Emit one step-N-1 token for ``seq``, which by now may be
        running, retiring (finished at dispatch), or preempted (its
        request re-queued; the token still belongs to the stream and
        re-admission folds it into the recompute prefix).  A request
        cancelled/expired while its token was in flight drops it."""
        seq.inflight -= 1
        req = seq.req
        if req.done:
            return
        if req.phase == 2:               # its first token ends "prefill"
            self._close_phase(req, self.trace.now_us())
        _emit(req, tok)
        self.metrics.on_token(req.uid)
        self.trace.instant(
            "first_token" if len(req.out_tokens) == 1 else "token",
            track=req_track(req.uid), cat="request", uid=req.uid,
            pos=seq.kv_len)
        if req.error == "callback":
            if seq in self.sched.running:
                self._retire(seq)
            elif req in self.sched.waiting:      # preempted victim
                self.sched.waiting.remove(req)
                self._fail_detached(req, "callback")
            else:                                # retiring: already freed
                self._fail_detached(req, "callback")
            return
        if seq.retiring and seq.inflight == 0:
            # the count-based retire decision was taken at dispatch;
            # a preempted seq can never complete here (its final token
            # would have flipped it to retiring instead)
            self._finalize_detached(req)

    def flush(self) -> None:
        """Sync and emit any in-flight async step without dispatching
        new work (drain point for the frontend and for mode mixing)."""
        prev, self._inflight = self._inflight, None
        self._sync_prev(prev)
        self._emit_counters(block=True)

    @property
    def has_inflight(self) -> bool:
        return self._inflight is not None

    # ------------------------------------------------------------------
    def _drain_tick_budget(self) -> None:
        """Tick budget exhausted: drain waiting/running requests as
        errored so callers polling ``req.done`` never hang, and so the
        pool's books balance (running seqs free their blocks)."""
        for seq in list(self.sched.running):
            seq.req.error = "tick_budget"
            self._retire(seq)
        while self.sched.waiting:
            req = self.sched.waiting.popleft()
            self._fail_detached(req, "tick_budget")

    def run(self, requests: list, max_ticks: int = 1000) -> list:
        for req in requests:
            self.submit(req)
        while self.sched.has_work() and self.ticks < max_ticks:
            self.step()
        if self.sched.has_work():
            self._drain_tick_budget()
        return self.finished

    def run_async(self, requests: list, max_ticks: int = 1000) -> list:
        """Drain a batch through the double-buffered tick (the asyncio
        frontend drives ``step_async`` itself; this mirrors :meth:`run`
        for benches and equivalence tests)."""
        for req in requests:
            self.submit(req)
        while (self.sched.has_work() or self._inflight is not None) \
                and self.ticks < max_ticks:
            self.step_async()
        self.flush()
        if self.sched.has_work():
            self._drain_tick_budget()
        return self.finished

    def probe_logits(self, prompt, continuation) -> np.ndarray:
        """Logits of one sequence through this engine's own jitted entry
        points (sharding, paged kernels and linear backends included):
        ``prompt`` prefilled as a single chunk, then one decode step per
        token of ``continuation`` (fed as given, so two engines probed
        with the same tokens stay aligned) on batch row 0.  Returns f32
        [1 + len(continuation), V].

        For checking the serving path against a reference; needs an
        idle engine.  Its blocks come from the pool and go back to it.
        """
        if self.sched.has_work() or self._inflight is not None:
            raise RuntimeError("probe_logits needs an idle engine")
        n, n_decode = len(prompt), len(continuation)
        blocks = self.pool.alloc("probe", self.pool.blocks_for(n + n_decode))
        if blocks is None or len(blocks) > self.max_blocks_per_seq:
            raise RuntimeError(f"no room for a {n + n_decode}-token probe")
        tables = np.full_like(self.tables, -1)
        tables[0, :len(blocks)] = blocks
        try:
            with self._attn_scope():
                logits, self.cache, _ = self._prefill_chunk(
                    self.params,
                    {"tokens": jnp.asarray(np.asarray(prompt, np.int32)[None])},
                    set_block_tables(self.cache, tables[:1]),
                    jnp.int32(0), jnp.int32(n - 1))
                out = [np.asarray(logits[0], np.float32)]
                for t in range(n_decode):
                    tokens = np.zeros((self.max_batch, 1), np.int32)
                    tokens[0, 0] = int(continuation[t])
                    posv = np.zeros(self.max_batch, np.int32)
                    posv[0] = n + t
                    logits, self.cache, _ = self._decode(
                        self.params, jnp.asarray(tokens),
                        set_block_tables(self.cache, tables),
                        jnp.asarray(posv))
                    out.append(np.asarray(logits[0], np.float32))
        finally:
            self.pool.free(blocks, "probe")
        return np.stack(out)


# ---------------------------------------------------------------------------
# contiguous fixed-slot engine (reference / fallback)
# ---------------------------------------------------------------------------


class ServeEngine:
    """Continuous batching over a fixed slot grid (one full ``cache_len``
    row per request).  The unit of work is a *slot*; one jit'd
    ``decode_step`` advances every active slot each tick.  Prefill runs
    per-request through a throwaway 1-row cache spliced into the grid;
    left-pads get negative positions, so the attention pos-mask makes
    padded prompts score exactly like unpadded ones in attention layers.
    (SSM layers have no position mask — pad embeddings still enter the
    conv/SSD state there, a documented residual simplification for the
    SSM/hybrid models this engine remains the fallback for.)"""

    def __init__(self, model: Model, params, *, slots: int = 8,
                 cache_len: int = 512, prefill_buckets=(32, 128, 512),
                 rng_seed: int = 0, pretune: bool = False):
        self.model = model
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.buckets = sorted(prefill_buckets)
        if pretune:
            _pretune(model, params, [1, slots, *self.buckets])
        self.cache = model.init_cache(slots, cache_len)
        self.slot_req: list = [None] * slots
        self.slot_pos = np.zeros(slots, np.int32)
        self.rng_seed = rng_seed
        self.rng = np.random.default_rng(rng_seed)
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(model.prefill)
        self.ticks = 0

    # ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        top = self.buckets[-1]          # longer prompts: round up to the
        return -(-n // top) * top       # top bucket (bounded trace count)

    def _free_slots(self):
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def add_request(self, req: Request) -> bool:
        """Prefill into a free slot; False if engine is full."""
        free = self._free_slots()
        if not free:
            return False
        plen = len(req.prompt)
        if plen == 0:
            req.error = "empty_prompt"
            req.done = True
            return True
        if plen >= self.cache_len - 1:       # can't hold prompt + 1 decode
            req.error = "too_long"           # reject, don't silently truncate
            req.done = True
            return True
        slot = free[0]
        bucket = self._bucket(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, -plen:] = req.prompt          # left-pad into the bucket
        # run prefill on a single-row cache then splice into the big cache;
        # start_pos < 0 gives the pads negative positions -> masked out of
        # attention and dead on insert (real tokens sit at 0..plen-1)
        small = self.model.init_cache(1, self.cache_len)
        logits, small = self.model.prefill(
            self.params, {"tokens": jnp.asarray(toks)}, small,
            jnp.int32(plen - bucket))
        self.cache = _splice_cache(self.cache, small, slot)
        first = _sample_host(req, np.asarray(logits)[0], self.rng_seed)
        _emit(req, first)
        if req.error == "callback" \
                or len(req.out_tokens) >= req.max_new_tokens:
            req.done = True                   # done (or its consumer broke):
            return True                       # slot stays free
        self.slot_req[slot] = req
        self.slot_pos[slot] = plen
        return True

    # ------------------------------------------------------------------
    def tick(self) -> list:
        """One decode step for every active slot; returns requests that
        retired this tick."""
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return []
        tokens = np.zeros((self.slots, 1), np.int32)
        for i in active:
            tokens[i, 0] = self.slot_req[i].out_tokens[-1]
        logits, self.cache = self._decode(
            self.params, jnp.asarray(tokens), self.cache,
            jnp.asarray(self.slot_pos))
        logits = np.asarray(logits)
        retired = []
        for i in active:
            req = self.slot_req[i]
            tok = _sample_host(req, logits[i], self.rng_seed)
            _emit(req, tok)
            self.slot_pos[i] += 1
            if req.error == "callback" \
                    or len(req.out_tokens) >= req.max_new_tokens \
                    or self.slot_pos[i] >= self.cache_len - 1:
                req.done = True
                retired.append(req)
                self.slot_req[i] = None
        self.ticks += 1
        return retired

    def run(self, requests: list, max_ticks: int = 1000) -> list:
        """Continuous batching: admit when slots free, tick until done."""
        pending = deque(requests)
        done = []
        while (pending or any(r is not None for r in self.slot_req)) \
                and self.ticks < max_ticks:
            while pending and self._free_slots():
                req = pending[0]
                if not self.add_request(req):
                    break
                pending.popleft()
                if req.done:
                    done.append(req)
            done.extend(self.tick())
        return done


def _splice_cache(big, small, slot: int):
    """Copy a 1-row cache into row ``slot`` of the engine cache.

    Leaves under a "scan" group are stacked with a leading layers axis,
    so their batch dim is axis 1, not axis 0."""
    def fix(path, b, s):
        stacked = any(isinstance(k, jax.tree_util.DictKey) and k.key == "scan"
                      for k in path)
        if stacked:
            return b.at[:, slot:slot + 1].set(s.astype(b.dtype))
        return b.at[slot:slot + 1].set(s.astype(b.dtype))
    return jax.tree_util.tree_map_with_path(fix, big, small)
