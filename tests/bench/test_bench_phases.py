"""The engine's request phase spans and program builds, read by the
benchmark on a tiny traced run on the CPU: every answered window request
has contiguous ``queue``, ``prefill_wait`` and ``prefill`` spans, the six
readers of them return numbers, and the four parts of time to first
token add up to its host-clock mean."""
import json
import math
import os

import pytest

from bench import harness, peaks
from repro import obs

SEED = 2**31 + 7

PARTS = ("frontend.submit_lag_mean_ms", "sched.admit_wait_mean_ms",
         "sched.prefill_wait_mean_ms", "engine.prefill_to_token_mean_ms")
NEW = PARTS + ("engine.builds_in_window", "setup.program_load_s")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from conftest import TINY_TRAFFIC, make_root, tiny_config
    from bench import spec
    tmp = str(tmp_path_factory.mktemp("phases"))
    # every metric, those that list the real cells included
    per_layer = [{k: v for k, v in m.items() if k != "workloads"}
                 for m in spec.load_benchmark()["per_layer"]]
    bdir = make_root(tmp, {"opt-6.7b-bcq3": tiny_config("opt-6.7b-bcq3")},
                     {"tiny": TINY_TRAFFIC},
                     [{"name": "opt.tiny", "config": "opt-6.7b-bcq3",
                       "traffic": "tiny", "chips": 1, "why": "tiny"}],
                     per_layer=per_layer)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mp = pytest.MonkeyPatch()
    # the chip's table, so that the device readers run on the CPU
    mp.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    kept = {}
    run_window = harness.run_window

    def keep(*a, **kw):
        w = run_window(*a, **kw)
        kept["recs"] = list(w.recs)
        return w
    mp.setattr(harness, "run_window", keep)
    try:
        out = harness.run_cell(bench, "opt.tiny", SEED, 2.0, True, 0.0,
                               require_tpu=False, bench_dir=bdir)
        tracer = obs.get_active()
    finally:
        mp.undo()
        obs.set_active(None)
    return out, tracer, kept["recs"]


def test_answered_requests_have_contiguous_phases(traced):
    out, tracer, recs = traced
    assert out["correct"] is True
    spans = {}
    for e in tracer.events:
        if e["name"] in ("queue", "prefill_wait", "prefill"):
            spans.setdefault(e["args"]["uid"], {})[e["name"]] = e
    answered = [r for r in recs if r.times]
    assert len(answered) == len(recs) > 0
    for r in answered:
        q, w, p = (spans[r.req.uid][n]
                   for n in ("queue", "prefill_wait", "prefill"))
        assert q["ts"] + q["dur"] == pytest.approx(w["ts"], abs=1e-3)
        assert w["ts"] + w["dur"] == pytest.approx(p["ts"], abs=1e-3)
        assert min(q["dur"], w["dur"], p["dur"]) >= 0


def test_the_six_readers_return_numbers(traced):
    out, _, _ = traced
    for name in NEW:
        v = out["metrics"][name]["value"]
        assert isinstance(v, (int, float)) and math.isfinite(v), name
        assert v >= 0, (name, v)
    assert out["metrics"]["setup.program_load_s"]["value"] > 0
    assert out["metrics"]["engine.builds_in_window"]["unit"] == "programs"


def test_the_four_parts_sum_to_the_mean_time_to_first_token(traced):
    out, _, recs = traced
    host_ms = sum(r.times[0] - r.due for r in recs) / len(recs) * 1e3
    parts_ms = sum(out["metrics"][n]["value"] for n in PARTS)
    assert parts_ms == pytest.approx(host_ms, abs=1.0)

