"""The harness end to end on the CPU at tiny sizes: it finds new cells by
their files alone, reads the per-layer metrics of a traced run, and its
check fails when the timed path is broken underneath."""
import json
import os

import pytest

from bench import harness, peaks, spec
from conftest import TINY_TRAFFIC, make_root, tiny_config
from repro.models.model import Model

SEED = 2**31 + 99


@pytest.fixture
def cpu_peaks(monkeypatch):
    # the chip's table, so that readers run on the CPU; a CPU number is
    # never reported as a device metric (run.py refuses a non-TPU device)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


NEW_METRIC = '''"""Most live rows of any decode step in the window."""
UNIT = "rows"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "output_tok_per_s"


def read(ctx):
    rows = [e["args"]["rows"] for e in ctx.obs("decode_dispatch", *ctx.window)
            if e["ph"] == "X"]
    return max(rows) if rows else None
'''


def _new_root(tmp_path, metric_unit="rows"):
    cfg = tiny_config("phi4-mini-bcq3")
    cfg["name"] = "newcfg"
    new = {"name": "sched.rows_max", "unit": metric_unit, "better": "higher",
           "source": "program_span", "layer": "scheduler",
           "moves": "output_tok_per_s", "workloads": ["newcfg.newmix"]}
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"] + [new]
    bdir = make_root(str(tmp_path), {"newcfg": cfg},
                     {"newmix": dict(TINY_TRAFFIC, mix="newmix")},
                     [{"name": "newcfg.newmix", "config": "newcfg",
                       "traffic": "newmix", "chips": 1, "why": "new"}],
                     per_layer=per_layer)
    with open(os.path.join(bdir, "metrics", "sched.rows_max.py"), "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(str(tmp_path), "BENCHMARK.json")) as f:
        return json.load(f), bdir


def test_new_config_mix_and_metric_by_files_alone(tmp_path, cpu_peaks):
    bench, bdir = _new_root(tmp_path)
    out = harness.run_cell(bench, "newcfg.newmix", SEED, 2.0, True, 0.0,
                           require_tpu=False, bench_dir=bdir)
    assert out["correct"] is True
    assert out["attempted"] == 12 and out["failed"] == 0
    assert 1 <= out["metrics"]["sched.rows_max"]["value"] <= 4
    assert out["metrics"]["sched.decode_rows_mean"]["unit"] == "rows"
    assert out["device"]["window_s"] > 0
    assert out["metrics"]["frontend.ttft_p95_ms"]["value"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "check"


def test_reader_must_agree_with_its_entry(tmp_path):
    bench, bdir = _new_root(tmp_path, metric_unit="%")
    entry = [m for m in bench["per_layer"] if m["name"] == "sched.rows_max"][0]
    with pytest.raises(spec.SpecError):
        spec.reader(entry, bdir)


def test_end_to_end_run(tiny_opt):
    bench, bdir = tiny_opt
    out = harness.run_cell(bench, "opt.tiny", SEED, 2.0, False, 0.0,
                           require_tpu=False, bench_dir=bdir)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"output_tok_per_s", "ttft_p50_ms",
                                   "itl_p99_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["check"]["short_outputs"] == {"value": 0, "limit": 0}


def _shift_tokens(self, params, tokens, cache, *a):
    # every token the decode step samples is shifted by one on the device
    toks, cache = _ORIG(self, params, tokens, cache, *a)
    return (toks + 1) % self.cfg.vocab_size, cache


def _keep_state(self, params, tokens, cache, *a):
    # the decode step returns the KV cache it was given: no token's keys
    # and values are ever written by a decode step
    toks, _ = _ORIG(self, params, tokens, cache, *a)
    return toks, cache


_ORIG = Model.decode_and_sample


@pytest.mark.parametrize("fault", [None, _shift_tokens, _keep_state],
                         ids=["sound", "token_altered", "state_unchanged"])
def test_broken_timed_path_fails(tiny_opt, monkeypatch, fault):
    if fault is not None:
        monkeypatch.setattr(Model, "decode_and_sample", fault)
    bench, bdir = tiny_opt
    cfg_path = os.path.join(bdir, "configs", "opt-6.7b-bcq3.json")
    c = json.load(open(cfg_path))
    # width 512 and a vocabulary of 8192 (test_bench_control.py): the
    # program's widest gap reads under 0.01 there, so 0.03 separates
    c.update(hidden_size=512, word_embed_proj_dim=512, head_dim=128,
             num_attention_heads=4, ffn_dim=2048, vocab_size=8192)
    c["check"]["max_logit_gap"] = 0.03
    json.dump(c, open(cfg_path, "w"))
    # answers long enough that a lost context changes the next token
    tr_path = os.path.join(bdir, "traffic", "tiny.json")
    t = json.load(open(tr_path))
    t["output_len"] = {"dist": "uniform", "min": 24, "max": 40}
    json.dump(t, open(tr_path, "w"))
    out = harness.run_cell(bench, "opt.tiny", SEED, 2.0, False, 0.0,
                           require_tpu=False, bench_dir=bdir)
    gap = out["check"]["max_logit_gap"]["value"]
    assert out["correct"] is (fault is None)
    assert (gap <= 0.03) is (fault is None), gap
