"""What a per-layer reader is handed: one traced run, on one clock.

A reader (``bench/metrics/<name>.py``) declares ``UNIT``, ``BETTER``,
``SOURCE``, ``LAYER`` and ``MOVES`` and defines ``read(ctx)``, which
returns a number or ``None`` when the run gave it nothing to read.  The
:class:`Context` joins three records of the traced run:

  * the profiler trace (:mod:`bench.devtrace`) of the last ``trace_s``
    seconds of the window;
  * the serving engine's own ``repro.obs`` events over the whole window
    (admissions, decode dispatches with their rows and request ids,
    prefill chunks with their start, length and bucket);
  * the requests the benchmark sent, with their due times.

Host times are ``time.perf_counter`` seconds; :meth:`Context.to_prof`
maps them onto the profiler's clock through the benchmark's anchor
annotation.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import time
from typing import List, Optional

from bench import counts, devtrace, peaks as peaks_mod

# Device operations of each kernel family, by one name rule.  Read off a
# chip trace by hand (PERF.md, section 3): each Pallas call appears in
# "XLA Ops" under its kernel's name, ``%bcq_matmul_tiled.<n> = ...``, and
# every kernel is named ``<what>_tiled``.  A name that starts ``paged_``
# is paged attention (``paged_attention``, ``paged_prefill``,
# ``paged_attention_mla``); one that holds ``_matmul`` or ``_gemm`` is a
# quantized linear (``bcq_matmul``, ``lut_gemm``, ``ternary_matmul``, a
# grouped expert ``*_matmul*``).  Matched on the op's own name, not on its
# operands.
KERNELS = {
    "gemm": re.compile(r"\w*_(matmul|gemm)\w*_tiled\b"),
    "attn": re.compile(r"paged_\w+_tiled\b"),
}
# the programs the window drives, by jitted function name
PROGRAMS = {"decode": "jit_decode_and_sample", "prefill": "jit_prefill_chunk"}
# ops that hold other ops (a scan over layers is a ``while``): left out of
# the top ops, whose time their children already show
CONTAINER_OPS = ("while", "conditional", "call")
# host spans an idle gap of the device is named after
HOST_SPANS = ("tick", "admission", "prefix_lookup", "decode_dispatch",
              "device_sync", "sample", "emit", "prefill_chunk")


@dataclasses.dataclass
class Context:
    engine: dict
    linears: List[counts.Linear]
    shape: counts.Shape
    window: tuple                 # host (open, close)
    trace_window: tuple           # host (start, stop) of the profiler
    device: devtrace.Trace
    tracer: object                # repro.obs.Tracer
    recs: list
    kind: str
    drain_end: float = 0.0

    def __post_init__(self):
        self.peaks = peaks_mod.peaks(self.kind)
        # obs timestamps are microseconds since the tracer's own zero
        self._obs_zero = time.perf_counter() - self.tracer.now_us() * 1e-6
        self._events = sorted(self.tracer.events, key=lambda e: e["ts"])

    # -- clocks ---------------------------------------------------------
    @property
    def trace_seconds(self) -> float:
        return self.trace_window[1] - self.trace_window[0]

    def to_prof(self, t_host: float) -> float:
        a = self.device.anchor()
        if a is None:
            raise RuntimeError("the trace holds no bench_anchor annotation")
        return a + (t_host - self.trace_window[0])

    def prof_window(self) -> tuple:
        return self.to_prof(self.trace_window[0]), \
            self.to_prof(self.trace_window[1])

    def obs_time(self, ev: dict) -> float:
        return self._obs_zero + ev["ts"] * 1e-6

    # -- engine events --------------------------------------------------
    def obs(self, name: str, lo: float = -1e300, hi: float = 1e300):
        return [e for e in self._events if e["name"] == name
                and lo <= self.obs_time(e) <= hi]

    def steps(self, lo: float, hi: float) -> List[counts.Step]:
        """Device steps dispatched in host ``[lo, hi]``, rebuilt from the
        engine's events: a row's context at a decode step is its prefill
        target plus the decode steps since its admission, plus the token
        being decoded.  A step span's ``step`` id joins the args of any
        later ``step_counters`` instant that names it into the step's
        ``counters``."""
        kv = {}
        out = []
        by_id = {}
        for e in self._events:
            a = e.get("args", {})
            if e["name"] == "admit":
                kv[a["uid"]] = a["prefill_target"]
            elif e["name"] == "step_counters" and a.get("step") in by_id:
                by_id[a["step"]].counters.update(
                    (k, v) for k, v in a.items() if k not in ("step", "tick"))
            elif e["name"] in ("decode_dispatch", "prefill_chunk") \
                    and e["ph"] == "X":
                t = self.obs_time(e)
                if e["name"] == "decode_dispatch":
                    ctx = []
                    for u in a["uids"]:
                        ctx.append(kv.get(u, 0) + 1)
                        kv[u] = kv.get(u, 0) + 1
                    step = counts.Step("decode", ctx)
                else:
                    step = counts.Step("prefill", [a["start"], a["length"]],
                                       a["bucket"])
                if "step" in a:
                    by_id[a["step"]] = step
                if lo <= t <= hi:
                    out.append(step)
        return out

    def traced_steps(self) -> List[counts.Step]:
        return self.steps(*self.trace_window)

    # -- device ---------------------------------------------------------
    def busy_s(self) -> float:
        return self.device.busy_s(*self.prof_window())

    def in_window(self, evs):
        lo, hi = self.prof_window()
        return [e for e in evs if e.start >= lo and e.start < hi]

    def kernel_ops(self, family: str):
        pat = KERNELS[family]
        return [e for e in self.in_window(self.device.ops())
                if pat.match(e.op)]

    def program_runs(self, kind: str):
        return self.in_window(self.device.modules(PROGRAMS[kind]))

    def breakdown(self) -> dict:
        """Top device operations by time, and the longest idle gaps named
        by the host span in progress."""
        tot = collections.Counter()
        mods = sorted(self.device.modules(), key=lambda m: m.start)
        starts = [m.start for m in mods]
        for e in self.in_window(self.device.ops()):
            if e.op.startswith(CONTAINER_OPS):
                continue
            i = bisect.bisect_right(starts, e.start) - 1
            mod = mods[i].name.split("(")[0] if i >= 0 \
                and e.start < mods[i].end else "?"
            tot[f"{mod}/{e.op}"] += e.dur
        lo, hi = self.prof_window()
        gaps = sorted(self.device.idle_gaps(lo, hi),
                      key=lambda g: g[0] - g[1])[:10]
        named = [[self.device.span_at(a, HOST_SPANS) or "no_host_span",
                  b - a] for a, b in gaps]
        return {"device_ops": [[n, s] for n, s in tot.most_common(10)],
                "idle_gaps": named}


def gemm_bound_s(m: int, ctx: Context, head_rows: int = 0,
                 experts_touched: Optional[int] = None) -> Optional[float]:
    """Least time of every quantized linear of one step on ``m`` rows,
    each call bounded on its own: a routed expert role's call per layer
    on ``m`` rows' top experts over the step's touched experts (``None``
    without ``experts_touched``), and a head stored as a bundle on the
    ``head_rows`` rows that produce logits."""
    dense, routed = counts.split_routed(ctx.linears)
    t = sum(lin.layers * peaks_mod.bound_s(
        counts.gemm(m, lin).flops, counts.gemm(m, lin).bytes, ctx.peaks)
        for lin in dense)
    if routed:
        if experts_touched is None:
            return None
        per = counts.touched_per_layer(routed, experts_touched)
        for lin in routed:
            w = counts.routed_gemm(m, lin, ctx.shape.experts_per_token, per)
            t += lin.layers * peaks_mod.bound_s(w.flops, w.bytes, ctx.peaks)
    if ctx.shape.head_gemm and head_rows:
        w = counts.lm_head(head_rows, ctx.shape)
        t += peaks_mod.bound_s(w.flops, w.bytes, ctx.peaks)
    return t
