"""Observability subsystem tests: tracer, exporters, metrics satellites,
glossary sync and the perf-trajectory gate.

Covers: span/instant recording over an injectable clock, ring-buffer
bounding with drop accounting, NullTracer no-op compatibility, the
active-tracer escape hatch ``tune.dispatch`` records kernel-config
resolutions through, Chrome trace-event export (structure, lane
metadata, JSON round-trip, validator catching injected corruption),
per-request timeline filtering, a real traced engine run producing >= 1
span per serving phase plus per-request tracks, request phase spans that
tile the wait for the first token, uids on every batched engine span,
program builds on ``engine/compile`` (one span per build, persistent-cache
loads marked, builds before attach replayed at non-negative times, the
process log bounded), the metrics satellites (exact histogram extremes
under reservoir eviction, per-path decode-step counts, first-admission
throughput clock, running per-tick gauges), README glossary sync with
``ServeMetrics.summary()``, and ``benchmarks.compare_trajectory``
failing on injected regressions while passing identity/improvement.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.obs import (BUILDS, ENGINE_TRACKS, NULL, ORIGIN, SCHEMA_VERSION,
                       NullTracer, Tracer, activate, format_timeline,
                       get_active, record_kernel_config, replay_builds,
                       req_track, save_chrome, set_active, timeline,
                       to_chrome, validate_chrome)
from repro.obs.trace import BUILD_EVENT, BuildLog
from repro.serve.metrics import Histogram, ServeMetrics

import benchmarks.compare_trajectory as traj


class _Clock:
    """Deterministic manual clock (seconds)."""

    def __init__(self):
        self.t = 0.0

    def tick(self, dt=1e-3):
        self.t += dt

    def __call__(self):
        return self.t


def _tracer():
    return Tracer(clock=_Clock(), capacity=256)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_records_complete_event_with_duration(self):
        tr = _tracer()
        tr.clock.tick(0.001)                     # 1000us after t0
        with tr.span("prefill_chunk", track="engine/prefill", uid=3):
            tr.clock.tick(0.002)                 # body takes 2000us
        (ev,) = tr.events
        assert ev["name"] == "prefill_chunk" and ev["ph"] == "X"
        assert ev["track"] == "engine/prefill"
        assert ev["ts"] == pytest.approx(1000.0)
        assert ev["dur"] == pytest.approx(2000.0)
        assert ev["args"]["uid"] == 3

    def test_span_emits_even_when_body_raises(self):
        tr = _tracer()
        with pytest.raises(RuntimeError):
            with tr.span("tick"):
                raise RuntimeError("boom")
        assert [e["name"] for e in tr.events] == ["tick"]

    def test_instant_and_tick_tagging(self):
        tr = _tracer()
        tr.instant("submit", track=req_track(7), uid=7)
        tr.tick = 4
        tr.instant("admit", track=req_track(7), uid=7)
        a, b = tr.events
        assert a["ph"] == "i" and "tick" not in a["args"]  # tick unset: -1
        assert b["args"]["tick"] == 4

    def test_ring_buffer_bounds_and_counts_drops(self):
        tr = Tracer(clock=_Clock(), capacity=10)
        for i in range(25):
            tr.instant(f"e{i}")
        assert len(tr.events) == 10
        assert tr.dropped == 15 and tr.total == 25
        # newest events win
        assert tr.events[-1]["name"] == "e24"
        tr.clear()
        assert tr.events == [] and tr.dropped == 0

    def test_tracks_engine_lanes_first_in_catalogue_order(self):
        tr = _tracer()
        tr.instant("x", track=req_track(2))
        tr.instant("x", track="engine/sample")
        tr.instant("x", track="engine/tick")
        assert tr.tracks() == ["engine/tick", "engine/sample", "req/2"]
        assert set(ENGINE_TRACKS) >= {"engine/tick", "engine/sample"}

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            Tracer(clock=_Clock(), capacity=0)

    def test_null_tracer_is_inert_and_api_compatible(self):
        n = NullTracer()
        with n.span("tick", track="engine/tick", free=3):
            n.instant("admit", uid=1)
        n.emit("x", "i", 0.0, "engine/tick")
        assert n.events == [] and n.tracks() == [] and n.dropped == 0
        assert NULL.now_us() == 0.0

    def test_default_clock_counts_from_the_process_origin(self):
        import time
        a = Tracer()
        b = Tracer()
        assert a.origin == b.origin == ORIGIN
        # an earlier process-clock reading maps to a non-negative time
        t = time.perf_counter() - 1e-3
        assert 0.0 <= a.at_us(t) == pytest.approx(b.at_us(t))
        assert a.at_us(t) < a.now_us()
        # an injected clock still counts from construction
        assert _tracer().origin == 0.0 and _tracer().now_us() == 0.0

    def test_phase_is_a_request_span_with_its_uid(self):
        tr = _tracer()
        tr.tick = 2
        tr.phase("queue", 5, 10.0, 35.0)
        (ev,) = tr.events
        assert (ev["name"], ev["ph"], ev["track"], ev["cat"]) == \
            ("queue", "X", "req/5", "request")
        assert ev["ts"] == 10.0 and ev["dur"] == 25.0
        assert ev["args"] == {"uid": 5, "tick": 2}
        NULL.phase("queue", 5, 10.0, 35.0)             # no-op
        assert NULL.events == []


class TestActiveTracer:
    def test_activate_restores_previous(self):
        tr = _tracer()
        assert get_active() is None
        with activate(tr):
            assert get_active() is tr
            with activate(None):
                assert get_active() is None
            assert get_active() is tr
        assert get_active() is None

    def test_record_kernel_config_noop_without_active(self):
        from repro.tune.space import heuristic_config
        cfg = heuristic_config("lut_gemm", b=4, m=64, n=128, mu=4,
                               group_size=32)
        set_active(None)
        record_kernel_config("lut_gemm", "heuristic", cfg)  # must not raise

    def test_dispatch_records_resolution_on_active_tracer(self, monkeypatch):
        from repro.tune.dispatch import kernel_config
        monkeypatch.setenv("REPRO_TUNE", "off")   # deterministic: heuristic
        tr = _tracer()
        with activate(tr):
            cfg = kernel_config("lut_gemm", b=4, m=64, n=128,
                                dtype=np.float32, mu=4, group_size=32)
        (ev,) = tr.events
        assert ev["name"] == "kernel_config:lut_gemm"
        assert ev["track"] == "engine/kernel"
        assert ev["args"]["source"] == "heuristic"
        assert ev["args"]["config"] == cfg.to_dict()
        assert ev["args"]["m"] == 64


# ---------------------------------------------------------------------------
# chrome export + timeline
# ---------------------------------------------------------------------------


def _populated_tracer():
    tr = _tracer()
    tr.tick = 0
    with tr.span("tick", track="engine/tick", running=1):
        tr.clock.tick()
        with tr.span("admission", track="engine/admission"):
            tr.clock.tick()
            tr.instant("admit", track=req_track(0), uid=0)
        tr.instant("token", track=req_track(1), uid=1, pos=5)
    return tr


class TestChromeExport:
    def test_structure_lanes_and_validation(self):
        tr = _populated_tracer()
        obj = to_chrome(tr)
        assert validate_chrome(obj) == []
        evs = obj["traceEvents"]
        procs = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert procs == {"engine phases", "requests"}
        lanes = {e["args"]["name"]: (e["pid"], e["tid"]) for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        # engine lanes on pid 0, request lanes on pid 1, uid-sorted
        assert lanes["engine/tick"][0] == 0
        assert lanes["req/0"] == (1, 0) and lanes["req/1"] == (1, 1)
        # instants carry thread scope; spans carry dur
        inst = next(e for e in evs if e["ph"] == "i")
        assert inst["s"] == "t"
        span = next(e for e in evs if e["ph"] == "X")
        assert span["dur"] >= 0
        assert obj["otherData"]["schema_version"] == SCHEMA_VERSION
        assert obj["otherData"]["clock"] == "_Clock"
        assert obj["otherData"]["origin"] == 0.0
        assert to_chrome(Tracer())["otherData"]["clock"] == "perf_counter"
        assert to_chrome(Tracer())["otherData"]["origin"] == ORIGIN

    def test_json_round_trip_still_validates(self, tmp_path):
        tr = _populated_tracer()
        path = save_chrome(tr, str(tmp_path / "trace.json"))
        with open(path) as f:
            loaded = json.load(f)
        assert validate_chrome(loaded) == []
        assert loaded == json.loads(json.dumps(to_chrome(tr),
                                               sort_keys=True))

    def test_validator_catches_injected_corruption(self):
        good = to_chrome(_populated_tracer())
        assert validate_chrome({"nope": 1}) == ["missing traceEvents"]

        bad = json.loads(json.dumps(good))
        bad["otherData"]["schema_version"] = 99
        assert any("schema_version" in e for e in validate_chrome(bad))

        bad = json.loads(json.dumps(good))
        span = next(e for e in bad["traceEvents"] if e["ph"] == "X")
        del span["dur"]
        assert any("bad dur" in e for e in validate_chrome(bad))

        bad = json.loads(json.dumps(good))
        next(e for e in bad["traceEvents"] if e["ph"] == "i")["ph"] = "Z"
        assert any("unexpected ph" in e for e in validate_chrome(bad))

        bad = json.loads(json.dumps(good))
        bad["traceEvents"] = [e for e in bad["traceEvents"]
                              if not (e["ph"] == "M"
                                      and e.get("args", {}).get("name")
                                      == "req/0")]
        assert any("no thread_name" in e for e in validate_chrome(bad))

    def test_timeline_uid_filter_includes_engine_events_naming_it(self):
        tr = _populated_tracer()
        rows = timeline(tr, uid=0)
        names = [r["name"] for r in rows]
        # req/0's own instant plus the engine admission span? admission
        # span has no uid arg -> excluded; 'admit' instant included
        assert "admit" in names
        assert "token" not in names                    # that's uid 1
        all_rows = timeline(tr)
        assert len(all_rows) == len(tr.events)
        assert all_rows == sorted(all_rows, key=lambda r: r["ts_ms"])

    def test_format_timeline_clips_and_reports(self):
        tr = _populated_tracer()
        out = format_timeline(tr, max_rows=2)
        assert "(2 more rows)" in out
        assert "track" in out.splitlines()[0]


# ---------------------------------------------------------------------------
# traced engine run (integration)
# ---------------------------------------------------------------------------


class TestEngineTracing:
    @pytest.fixture(scope="class")
    def traced_engine(self):
        import jax
        import jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models import Model
        from repro.serve import PagedServeEngine, Request

        cfg = get_reduced("opt_6_7b").replace(remat=False, dtype="float32")
        model = Model(cfg)
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16
            else x, model.init(jax.random.PRNGKey(0)))
        tracer = Tracer()
        eng = PagedServeEngine(model, params, num_blocks=16, block_size=8,
                               max_batch=2, max_seq_len=64,
                               prefill_buckets=(16,), tracer=tracer)
        rng = np.random.default_rng(3)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size, (9 + 3 * i,)),
                        max_new_tokens=3) for i in range(2)]
        done = eng.run(reqs, max_ticks=100)
        set_active(None)
        assert len(done) == 2 and all(r.error is None for r in done)
        return tracer, eng

    @pytest.fixture(scope="class")
    def traced_run(self, traced_engine):
        return traced_engine[0]

    def test_every_serving_phase_has_a_span(self, traced_run):
        spans = {e["name"] for e in traced_run.events if e["ph"] == "X"}
        for phase in ("tick", "admission", "prefill_chunk",
                      "decode_dispatch", "device_sync", "sample"):
            assert phase in spans, (phase, sorted(spans))

    def test_per_request_tracks_and_lifecycle_instants(self, traced_run):
        tracks = set(traced_run.tracks())
        assert {"req/0", "req/1"} <= tracks
        by_track = {}
        for e in traced_run.events:
            by_track.setdefault(e["track"], []).append(e["name"])
        for uid in (0, 1):
            names = by_track[req_track(uid)]
            for ev in ("submit", "admit", "first_token", "complete"):
                assert ev in names, (uid, ev, names)
            # lifecycle ordering on the request's own lane
            assert names.index("submit") < names.index("admit") \
                < names.index("first_token") < names.index("complete")

    def test_real_trace_exports_valid_chrome_json(self, traced_run):
        assert validate_chrome(to_chrome(traced_run)) == []
        assert traced_run.dropped == 0

    def test_request_phases_tile_the_wait_for_the_first_token(
            self, traced_run):
        for uid in (0, 1):
            evs = {e["name"]: e for e in traced_run.events
                   if e["track"] == req_track(uid)}
            q, w, p = evs["queue"], evs["prefill_wait"], evs["prefill"]
            assert all(e["ph"] == "X" and e["args"]["uid"] == uid
                       for e in (q, w, p))
            # the phase stamps sit just before the lifecycle instants
            assert 0 <= evs["submit"]["ts"] - q["ts"] < 1e3
            assert q["ts"] + q["dur"] == pytest.approx(w["ts"], abs=1e-3)
            assert w["ts"] + w["dur"] == pytest.approx(p["ts"], abs=1e-3)
            end = p["ts"] + p["dur"]
            assert 0 <= evs["first_token"]["ts"] - end < 1e3
            first_chunk = min(e["ts"] for e in traced_run.events
                              if e["name"] == "prefill_chunk"
                              and e["ph"] == "X" and e["args"]["uid"] == uid)
            assert w["ts"] + w["dur"] <= first_chunk

    def test_batched_spans_name_their_requests(self, traced_run):
        for name in ("decode_dispatch", "device_sync", "sample"):
            spans = [e for e in traced_run.events if e["name"] == name]
            assert spans and all(e["args"]["uids"] for e in spans), name
        served = {r["name"] for r in timeline(traced_run, uid=1)
                  if r["track"].startswith("engine/")}
        assert {"decode_dispatch", "device_sync", "sample",
                "prefill_chunk"} <= served

    def test_first_build_in_the_run_gives_one_compile_span(self,
                                                           traced_run):
        builds = [e for e in traced_run.events if e["name"] == "compile"]
        assert all(e["track"] == "engine/compile" and e["ph"] == "X"
                   and e["ts"] >= 0 and e["dur"] >= 0 for e in builds)
        for prog in ("jit(decode_step)", "jit(prefill_chunk)"):
            (ev,) = [e for e in builds if e["args"]["fun_name"] == prog]
            assert ev["args"]["cache_hit"] is False
            assert ev["args"]["tick"] >= 0          # built inside a tick

    def test_builds_before_attach_are_replayed(self, traced_run):
        # the parameters were drawn before the engine attached its tracer
        first_submit = min(e["ts"] for e in traced_run.events
                           if e["name"] == "submit")
        early = [e for e in traced_run.events if e["name"] == "compile"
                 and e["ts"] + e["dur"] <= first_submit]
        assert early and all(e["ts"] >= 0 for e in early)

    def test_builds_inside_ticks_are_counted(self, traced_engine):
        tracer, eng = traced_engine
        built = eng.metrics.summary()["counters"]["programs_built"]
        in_ticks = [e for e in tracer.events if e["name"] == "compile"
                    and "tick" in e["args"]]
        assert built >= 2 and built == len(in_ticks)

    def test_untraced_engine_holds_null_tracer(self):
        # constructing engines is expensive; check the default wiring on
        # the scheduler level instead of building a second engine
        from repro.serve import BlockPool, Scheduler
        sched = Scheduler(BlockPool(num_blocks=4, block_size=4), rows=2,
                          buckets=(16,), max_blocks_per_seq=4)
        assert isinstance(sched.trace, NullTracer)


# ---------------------------------------------------------------------------
# program builds
# ---------------------------------------------------------------------------


_CACHE_PROG = """
import json, sys, tempfile
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import repro.serve
from repro import obs
tracer = obs.Tracer()
obs.set_active(tracer)
f = lambda x: jnp.sin(x) * 2
x = jnp.ones((8,))
jax.jit(f)(x).block_until_ready()
jax.clear_caches()                       # the second build loads from disk
jax.jit(f)(x).block_until_ready()
print(json.dumps([e["args"]["cache_hit"] for e in tracer.events
                  if e["name"] == "compile"
                  and e["args"]["fun_name"] == "jit(<lambda>)"]))
"""


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_kv_page_span_args_match_host_count(monkeypatch, mode):
    """``kv_pages_live`` / ``kv_pages_grid`` on every ``decode_dispatch``
    and ``prefill_chunk`` span equal a count made here from the tables
    and positions each device call received: per row, the pages its
    newest query sees (0 for a row parked on the trash block), and rows
    x ``max_blocks_per_seq``.  Two requests on four rows leave idle
    rows in every decode step."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.models import Model
    from repro.serve import PagedServeEngine, Request
    import repro.serve.engine as engine_mod

    cfg = get_reduced("opt_6_7b").replace(remat=False, dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tracer = Tracer()
    bs, max_seq = 8, 64
    eng = PagedServeEngine(model, params, num_blocks=24, block_size=bs,
                           max_batch=4, max_seq_len=max_seq,
                           prefill_buckets=(16,), tracer=tracer)
    calls = {"decode": [], "prefill": []}
    tables_seen = []
    real_set = engine_mod.set_block_tables

    def set_tables(cache, tables):
        tables_seen.append(np.array(tables))
        return real_set(cache, tables)
    monkeypatch.setattr(engine_mod, "set_block_tables", set_tables)

    def record(kind, fn, q_pos):
        def wrapped(*args):
            calls[kind].append((tables_seen[-1], q_pos(args)))
            return fn(*args)
        return wrapped
    step = "_decode_sample" if mode == "async" else "_decode"
    setattr(eng, step, record("decode", getattr(eng, step),
                              lambda a: np.asarray(a[3])))
    eng._prefill_chunk = record(
        "prefill", eng._prefill_chunk,
        lambda a: np.array([int(a[3]) + int(a[4])]))

    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, (n,)),
                    max_new_tokens=4) for i, n in enumerate((9, 21))]
    done = (eng.run_async if mode == "async" else eng.run)(reqs,
                                                          max_ticks=100)
    set_active(None)
    assert len(done) == 2 and all(r.error is None for r in done)

    pages = max_seq // bs

    def host_count(tables, q_pos):
        live = 0
        for row, qp in zip(tables, q_pos):
            if row[0] >= 0 and qp >= 0:
                live += min(int(qp) // bs + 1, pages)
        return live, len(tables) * pages

    for kind, span in (("decode", "decode_dispatch"),
                       ("prefill", "prefill_chunk")):
        got = [(e["args"]["kv_pages_live"], e["args"]["kv_pages_grid"])
               for e in tracer.events if e["name"] == span and e["ph"] == "X"]
        want = [host_count(*c) for c in calls[kind]]
        assert got and got == want, (kind, got, want)
        shares = [live / grid for live, grid in want]
        summary = eng.metrics.summary()["paged_kernel"]
        assert summary["kv_pages_live_share"][kind] == pytest.approx(
            sum(shares) / len(shares))
    # idle rows were parked on the trash block and counted 0 pages
    assert any((t[:, 0] < 0).any() for t, _ in calls["decode"])
    assert all(live < grid for live, grid in
               (host_count(*c) for c in calls["decode"]))


class TestProgramBuilds:
    def test_persistent_cache_load_is_marked(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
        env["PYTHONPATH"] = os.pathsep.join(
            [src] + [p for p in [env.get("PYTHONPATH")] if p])
        out = subprocess.run([sys.executable, "-c", _CACHE_PROG],
                             env=env, capture_output=True, text=True,
                             timeout=300, cwd=str(tmp_path))
        assert out.returncode == 0, out.stderr[-2000:]
        assert json.loads(out.stdout.splitlines()[-1]) == [False, True]

    def test_builds_before_attach_arrive_with_non_negative_times(self):
        import jax
        import jax.numpy as jnp
        import repro.serve  # noqa: F401  (installs the listener)
        set_active(None)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
        tr = Tracer()
        replay_builds(tr)
        (ev,) = [e for e in tr.events if e["name"] == "compile"
                 and e["ts"] + e["dur"] >= 0
                 and e["args"]["fun_name"] == "jit(<lambda>)"][-1:]
        assert 0 <= ev["ts"] and ev["ts"] + ev["dur"] <= tr.now_us()
        n = len(tr.events)
        replay_builds(tr)                  # nothing is written twice
        assert len(tr.events) == n

    def test_live_build_goes_to_the_active_tracer_once(self):
        import jax
        import jax.numpy as jnp
        import repro.serve  # noqa: F401
        x = jnp.arange(3)
        tr = Tracer()
        replay_builds(tr)
        before = len(tr.events)
        with activate(tr):
            jax.jit(lambda x: x - 7)(x).block_until_ready()
        new = [e for e in tr.events[before:] if e["name"] == "compile"]
        assert [e["args"]["fun_name"] for e in new] == ["jit(<lambda>)"]
        n = len(tr.events)
        replay_builds(tr)                  # already written live
        assert len(tr.events) == n

    def test_build_log_is_bounded_and_untraced_builds_keep_no_event(self):
        log = BuildLog(capacity=3)
        set_active(None)
        for i in range(10):
            log.on_duration(BUILD_EVENT, 0.5, fun_name=f"jit(p{i})")
        log.on_duration("/jax/other", 0.5)                 # not a build
        assert log.total == 10 and len(log.records) == 3
        assert [r["fun_name"] for r in log.records] == \
            ["jit(p7)", "jit(p8)", "jit(p9)"]
        assert NULL.events == []
        assert len(BUILDS.records) <= BUILDS.records.maxlen

    def test_untraced_engine_run_records_nothing(self):
        import jax
        from repro.configs import get_reduced
        from repro.models import Model
        from repro.serve import PagedServeEngine, Request
        cfg = get_reduced("opt_6_7b").replace(remat=False, dtype="float32")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        set_active(None)
        eng = PagedServeEngine(model, params, num_blocks=16, block_size=8,
                               max_batch=2, max_seq_len=64,
                               prefill_buckets=(16,))
        rng = np.random.default_rng(4)
        reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, (7,)),
                        max_new_tokens=3) for i in range(2)]
        done = eng.run_async(reqs, max_ticks=100)
        assert all(r.error is None for r in done)
        assert eng.trace is NULL and get_active() is None
        assert NULL.events == [] and NULL.total == 0
        assert eng.metrics.summary()["counters"]["programs_built"] >= 1
        assert len(BUILDS.records) <= BUILDS.records.maxlen


# ---------------------------------------------------------------------------
# metrics satellites
# ---------------------------------------------------------------------------


class TestMetricsSatellites:
    def test_histogram_extremes_exact_under_reservoir_eviction(self):
        h = Histogram(max_samples=8)
        rng = np.random.default_rng(1)
        vals = rng.uniform(1e-4, 1.0, 500)
        vals[37] = 7.5                     # true max, early: will be
        vals[11] = 1e-6                    # evicted from an 8-slot pool
        for v in vals:
            h.observe(float(v))
        s = h.summary()
        assert s["max"] == 7.5 and s["min"] == 1e-6
        assert 7.5 not in h._samples or 1e-6 not in h._samples \
            or len(h._samples) == 8
        # percentile(100) is what max used to be — the reservoir lost it
        assert h.percentile(100) <= s["max"]
        assert set(s) == {"n", "mean", "p50", "p95", "min", "max"}

    def test_histogram_empty_extremes_are_zero(self):
        s = Histogram().summary()
        assert s["min"] == 0.0 and s["max"] == 0.0 and s["n"] == 0

    def test_decode_path_counts_survive_mixed_runs(self):
        m = ServeMetrics(clock=_Clock())
        assert m.decode_path is None
        m.on_decode_step(2, 10, 20, "fused")
        assert m.decode_path == "fused"
        m.on_decode_step(1, 5, 10, "gather")
        m.on_decode_step(1, 5, 10, "fused")
        # the old last-write string would report "fused" and hide the
        # gather step entirely
        assert m.decode_path == "mixed"
        pk = m.summary()["paged_kernel"]
        assert pk["path"] == "mixed"
        assert pk["steps_by_path"] == {"fused": 2, "gather": 1}

    def test_per_tick_gauges_keep_mean_and_peak_only(self):
        m = ServeMetrics(clock=_Clock())
        for occ, act in ((0.25, 1), (0.75, 3), (0.5, 2)):
            m.on_tick(occ, act, logical_blocks=6, physical_blocks=3,
                      prefix_cached=act)
        s = m.summary()
        assert s["occupancy"] == {"mean": pytest.approx(0.5), "peak": 0.75}
        assert s["peak_active"] == 3
        assert s["effective_capacity"] == {"mean": 2.0, "peak": 2.0}
        assert s["prefix_cache"]["cached_blocks_peak"] == 3
        assert not isinstance(m.occupancy, list)
        assert m.occupancy.n == 3

    def test_throughput_clock_starts_at_first_admission(self):
        clk = _Clock()
        m = ServeMetrics(clock=clk)
        clk.t = 10.0                       # long idle warm-up after init
        m.on_submit(0)
        clk.t = 11.0
        m.on_admit(0)                      # clock anchors HERE
        clk.t = 11.5
        for _ in range(5):
            m.on_token(0)
        clk.t = 12.0
        # 5 tokens over 1s since first admission — not over 12s since
        # construction (which would report ~0.42 tok/s)
        assert m.throughput() == pytest.approx(5.0)
        m2 = ServeMetrics(clock=_Clock())
        assert m2.throughput() == 0.0      # nothing admitted: no div-by-0


# ---------------------------------------------------------------------------
# README glossary sync
# ---------------------------------------------------------------------------


def _summary_keys(d, prefix=""):
    keys = set()
    for k, v in d.items():
        keys.add(k)
        if isinstance(v, dict):
            keys |= _summary_keys(v)
    return keys


def test_readme_glossary_documents_every_summary_key():
    """Every key ``ServeMetrics.summary()`` emits must appear (in
    backticks) in the README "Serving metrics glossary" section, so the
    uploaded ``serve-metrics`` artifact stays self-describing.  Brace
    groups like ``kv_bytes_per_token_{fused,gathered}`` expand."""
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    with open(os.path.join(root, "README.md")) as f:
        text = f.read()
    start = text.index("### Serving metrics glossary")
    section = text[start:]
    section = section[:section.index("### ", 4)]

    import re
    documented = set()
    for tok in re.findall(r"`([^`\n]+)`", section):
        m = re.fullmatch(r"(\w*)\{([\w,]+)\}(\w*)", tok)
        if m:
            documented |= {m.group(1) + mid + m.group(3)
                           for mid in m.group(2).split(",")}
        else:
            documented.add(tok)

    summary = ServeMetrics(clock=_Clock()).summary()
    missing = _summary_keys(summary) - documented
    assert not missing, (
        f"summary() keys missing from the README glossary: "
        f"{sorted(missing)} — document them in 'Serving metrics glossary'")


# ---------------------------------------------------------------------------
# perf-trajectory gate
# ---------------------------------------------------------------------------


def _bench(scalars, bench="serve", schema=traj.SCHEMA_VERSION):
    return {"schema_version": schema, "bench": bench, "scalars": scalars}


def _s(value, direction="higher", rel_tol=0.0, **bounds):
    d = {"value": value, "direction": direction, "rel_tol": rel_tol}
    d.update(bounds)
    return d


class TestTrajectoryGate:
    def test_identity_and_improvement_pass(self):
        base = _bench({"tok_s": _s(100.0, "higher", 0.1),
                       "ttft": _s(5.0, "lower", 0.1)})
        fails, rows = traj.compare(base, base)
        assert fails == [] and all(r["status"] == "ok" for r in rows)
        cur = _bench({"tok_s": _s(150.0), "ttft": _s(4.0)})
        fails, rows = traj.compare(cur, base)
        assert fails == []
        assert {r["status"] for r in rows} == {"improved"}

    def test_regression_beyond_tolerance_fails(self):
        base = _bench({"tok_s": _s(100.0, "higher", 0.1),
                       "ttft": _s(5.0, "lower", 0.1)})
        cur = _bench({"tok_s": _s(89.9), "ttft": _s(5.51)})
        fails, rows = traj.compare(cur, base)
        assert len(fails) == 2
        assert all(r["status"] == "REGRESSED" for r in rows)
        # within tolerance: both pass
        cur = _bench({"tok_s": _s(90.1), "ttft": _s(5.49)})
        assert traj.compare(cur, base)[0] == []

    def test_absolute_bounds_trump_relative_slack(self):
        base = _bench({"overhead": _s(2.0, "lower", 10.0, abs_max=5.0),
                       "speedup": _s(1.5, "higher", 0.9, abs_min=1.0)})
        cur = _bench({"overhead": _s(5.5), "speedup": _s(0.99)})
        fails, _ = traj.compare(cur, base)
        assert len(fails) == 2
        assert any("abs_max" in f for f in fails)
        assert any("abs_min" in f for f in fails)
        cur = _bench({"overhead": _s(4.9), "speedup": _s(1.01)})
        assert traj.compare(cur, base)[0] == []

    def test_missing_tracked_scalar_is_a_failure(self):
        base = _bench({"tok_s": _s(100.0), "ttft": _s(5.0, "lower")})
        cur = _bench({"tok_s": _s(100.0)})
        fails, rows = traj.compare(cur, base)
        assert len(fails) == 1 and "coverage" in fails[0]
        assert any(r["status"] == "MISSING" for r in rows)

    def test_new_scalar_reported_not_failed(self):
        base = _bench({"tok_s": _s(100.0)})
        cur = _bench({"tok_s": _s(100.0), "shiny": _s(1.0)})
        fails, rows = traj.compare(cur, base)
        assert fails == []
        assert any(r["scalar"] == "shiny" and "new" in r["status"]
                   for r in rows)

    def test_bench_name_mismatch_fails(self):
        fails, _ = traj.compare(_bench({}, bench="serve"),
                                _bench({}, bench="kernels"))
        assert fails and "mismatch" in fails[0]

    def test_baselines_gate_fields_win(self):
        # a regressing run cannot loosen its own tolerance: the current
        # file's rel_tol/direction are ignored
        base = _bench({"tok_s": _s(100.0, "higher", 0.0)})
        cur = _bench({"tok_s": _s(50.0, "higher", 0.99)})
        fails, _ = traj.compare(cur, base)
        assert len(fails) == 1

    def test_main_exit_codes_and_schema_gate(self, tmp_path):
        good = tmp_path / "good.json"
        bad = tmp_path / "bad.json"
        basep = tmp_path / "base.json"
        base = _bench({"tok_s": _s(100.0, "higher", 0.1)})
        basep.write_text(json.dumps(base))
        good.write_text(json.dumps(_bench({"tok_s": _s(99.0)})))
        bad.write_text(json.dumps(_bench({"tok_s": _s(10.0)})))
        assert traj.main([str(good), str(basep)]) == 0
        assert traj.main([str(bad), str(basep)]) == 1
        stale = tmp_path / "stale.json"
        stale.write_text(json.dumps(_bench({}, schema=0)))
        with pytest.raises(SystemExit):
            traj.main([str(stale), str(basep)])

    def test_committed_baselines_load_and_self_compare(self):
        root = os.path.join(os.path.dirname(__file__), os.pardir)
        for name in ("BENCH_serve.json", "BENCH_kernels.json"):
            path = os.path.join(root, "benchmarks", "baselines", name)
            data = traj.load(path)
            fails, rows = traj.compare(data, data)
            assert fails == [] and rows, name


def _step_events(tracer):
    spans = [e for e in tracer.events if e["ph"] == "X"
             and e["name"] in ("decode_dispatch", "prefill_chunk")]
    counters = [e for e in tracer.events if e["name"] == "step_counters"]
    return spans, counters


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_step_ids_and_counters_of_a_routed_model(mode):
    """Every dispatched step's span carries an increasing ``step`` id, and
    one later ``step_counters`` instant names it with the step's
    ``experts_touched`` and ``expert_load_max``.  One request on four
    rows: each decode step routes one real token, so each MoE layer
    touches exactly ``experts_per_token`` experts with one row each --
    the three idle rows route nowhere."""
    import jax
    from repro.configs import get_reduced
    from repro.models import Model
    from repro.serve import PagedServeEngine, Request

    cfg = get_reduced("deepseek_v2_lite").replace(remat=False)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tracer = Tracer()
    eng = PagedServeEngine(model, params, num_blocks=24, block_size=8,
                           max_batch=4, max_seq_len=64,
                           prefill_buckets=(16,), tracer=tracer)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=0, prompt=rng.integers(0, cfg.vocab_size, (21,)),
                    max_new_tokens=5)]
    done = (eng.run_async if mode == "async" else eng.run)(reqs,
                                                          max_ticks=100)
    set_active(None)
    assert len(done) == 1 and done[0].error is None
    spans, counters = _step_events(tracer)
    ids = [e["args"]["step"] for e in sorted(spans, key=lambda e: e["ts"])]
    assert ids == list(range(1, len(spans) + 1))
    by_step = {e["args"]["step"]: e for e in counters}
    assert sorted(by_step) == ids and len(counters) == len(ids)
    moe_layers = sum(cfg.mlp_kind(i) == "moe" for i in range(cfg.n_layers))
    k = cfg.experts_per_token
    for span in spans:
        c = by_step[span["args"]["step"]]
        assert c["ts"] >= span["ts"] + span["dur"]     # read after the span
        if span["name"] == "decode_dispatch":
            assert c["args"]["experts_touched"] == moe_layers * k
            assert c["args"]["expert_load_max"] == 1
        else:                      # 16 real tokens of a chunk, then 5
            n = span["args"]["length"]
            assert k <= c["args"]["expert_load_max"] <= n
            assert moe_layers * k <= c["args"]["experts_touched"] \
                <= moe_layers * cfg.n_experts


def test_dense_model_counters_carry_no_moe_keys():
    import jax
    from repro.configs import get_reduced
    from repro.models import Model
    from repro.serve import PagedServeEngine, Request

    cfg = get_reduced("phi4_mini_3_8b").replace(remat=False)
    model = Model(cfg)
    tracer = Tracer()
    eng = PagedServeEngine(model, model.init(jax.random.PRNGKey(0)),
                           num_blocks=16, block_size=8, max_batch=2,
                           max_seq_len=64, prefill_buckets=(16,),
                           tracer=tracer)
    rng = np.random.default_rng(4)
    done = eng.run_async([Request(uid=0, prompt=rng.integers(
        0, cfg.vocab_size, (9,)), max_new_tokens=3)], max_ticks=50)
    set_active(None)
    assert done[0].error is None
    spans, counters = _step_events(tracer)
    assert sorted(e["args"]["step"] for e in spans) == \
        sorted(e["args"]["step"] for e in counters)
    # the tracer's own tick stamp beside the step id, and nothing else
    assert all(set(e["args"]) <= {"step", "tick"} for e in counters)
