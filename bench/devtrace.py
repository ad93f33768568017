"""The profiler trace, reduced to what the per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into a plain :class:`Trace`:

  * ``devices``: for each chip's plane (``/device:TPU:<n>``), its lines
    by name -- ``XLA Modules`` holds one event per program execution
    (named after the jitted function, e.g. ``jit_decode_and_sample``),
    ``XLA Ops`` one per operation, Pallas kernels among them;
  * ``host``: the host threads' events, among them the serving engine's
    spans (``tick``, ``decode_dispatch``, ``device_sync``,
    ``prefill_chunk``, ...) that ``repro.obs.Tracer(profiler_bridge=
    True)`` writes as ``TraceAnnotation``s, and the benchmark's own
    ``bench_anchor`` annotation that ties the profiler's clock to the
    host's ``time.perf_counter``.

All times are seconds on the profiler's clock.  A ``Trace`` round-trips
through JSON (:meth:`Trace.to_json`), which is how the test of the
reduction keeps a small trace recorded on the chip.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES = "XLA Modules"
OPS = "XLA Ops"
ANCHOR = "bench_anchor"


@dataclasses.dataclass
class Ev:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def op(self) -> str:
        """The operation's own name: a TPU trace names an op by its whole
        HLO instruction (``%bcq_matmul_tiled.55 = f32[32,4096] custom-
        call(...)``), whose operands name other ops."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Dict[str, List[Ev]]]
    host: Dict[str, List[Ev]]

    # -- device side ----------------------------------------------------
    def device_lines(self, line: str) -> List[List[Ev]]:
        """That line of every device plane that has it."""
        return [lines[line] for lines in self.devices.values()
                if line in lines]

    def ops(self) -> List[Ev]:
        """Every operation of every device."""
        return [e for evs in self.device_lines(OPS) for e in evs]

    def modules(self, prefix: str = "") -> List[Ev]:
        """Program executions whose name starts with ``prefix``."""
        return [e for evs in self.device_lines(MODULES) for e in evs
                if e.name.startswith(prefix)]

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds in ``[lo, hi]`` in which an operation ran, averaged over
        the devices."""
        per = []
        for lines in self.devices.values():
            evs = lines.get(OPS) or lines.get(MODULES) or []
            per.append(stats.union_length(
                (max(e.start, lo), min(e.end, hi)) for e in evs
                if e.end > lo and e.start < hi))
        return sum(per) / len(per) if per else 0.0

    def idle_gaps(self, lo: float, hi: float) -> List[Tuple[float, float]]:
        """Intervals of ``[lo, hi]`` in which no operation ran on the
        first device."""
        if not self.devices:
            return []
        lines = next(iter(self.devices.values()))
        evs = sorted((e.start, e.end) for e in
                     (lines.get(OPS) or lines.get(MODULES) or []))
        gaps, cur = [], lo
        for s, e in evs:
            if s > cur:
                gaps.append((cur, min(s, hi)))
            cur = max(cur, e)
            if cur >= hi:
                break
        if cur < hi:
            gaps.append((cur, hi))
        return [(a, b) for a, b in gaps if b > a]

    # -- host side ------------------------------------------------------
    def host_events(self, name: Optional[str] = None) -> List[Ev]:
        return [e for evs in self.host.values() for e in evs
                if name is None or e.name == name]

    def anchor(self) -> Optional[float]:
        """Profiler-clock start of the benchmark's anchor annotation."""
        a = self.host_events(ANCHOR)
        return a[0].start if a else None

    def span_at(self, t: float, names) -> Optional[str]:
        """The innermost host span among ``names`` running at ``t``."""
        best = None
        for e in self.host_events():
            if e.name in names and e.start <= t < e.end:
                if best is None or e.start >= best.start:
                    best = e
        return best.name if best else None

    # -- (de)serialisation ---------------------------------------------
    def to_json(self) -> dict:
        enc = lambda evs: [[e.name, e.start, e.dur] for e in evs]
        return {"devices": {p: {l: enc(evs) for l, evs in lines.items()}
                            for p, lines in self.devices.items()},
                "host": {l: enc(evs) for l, evs in self.host.items()}}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        dec = lambda rows: [Ev(*row[:3]) for row in rows]
        return cls({p: {l: dec(r) for l, r in lines.items()}
                    for p, lines in d["devices"].items()},
                   {l: dec(r) for l, r in d["host"].items()})


def save(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def read_saved(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


def _ev(e) -> Ev:
    return Ev(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    prof = ProfileData.from_file(files[0])
    devices, host = {}, {}
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {ln.name: [_ev(e) for e in ln.events]
                                   for ln in plane.lines}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                evs = [_ev(e) for e in ln.events if e.duration_ns > 0]
                if evs:
                    host[ln.name] = evs
    return Trace(devices, host)
