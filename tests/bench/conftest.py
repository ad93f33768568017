"""Tiny cells for the benchmark's own tests.

``tiny_root`` builds a checkout-shaped directory holding a
``BENCHMARK.json``, tiny configuration and traffic files (the real
files' keys at the program's ``reduced()`` sizes) and copies of the
benchmark's references and metric readers, so the harness runs end to
end on the CPU in seconds.
"""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH = os.path.join(ROOT, "bench")

TINY = {
    "opt-6.7b-bcq3": dict(num_hidden_layers=2, hidden_size=64,
                          word_embed_proj_dim=64, num_attention_heads=4,
                          head_dim=16, ffn_dim=128, vocab_size=256,
                          max_position_embeddings=128),
    "phi4-mini-bcq3": dict(num_hidden_layers=2, hidden_size=64,
                           num_attention_heads=4, num_key_value_heads=2,
                           head_dim=16, intermediate_size=128,
                           vocab_size=256),
}
TINY_ENGINE = dict(block_size=8, max_batch=4, max_seq_len=128,
                   prefill_buckets=[16, 32], paged_kernel="auto",
                   prefix_cache=True, max_queue=64, kv_pool_tokens=768)
TINY_TRAFFIC = {
    "mix": "tiny",
    "users": "tests",
    "arrivals": {"kind": "poisson", "rate_per_s": 6.0},
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 40},
    "output_len": {"dist": "uniform", "min": 3, "max": 10},
    "shared_prefix_tokens": 0,
    "sampling": "greedy",
    "preroll_s": 1.0,
    "drain_s": 60,
    "trace_s": 1.0,
    "reference_sample": 4,
    "reference_min_tokens": 8,
}


def tiny_config(name: str, limit: float = 1.0) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        c = json.load(f)
    c.update(TINY[name])
    prog = dict(c["program"], scan_layers=False)
    if "max_seq_len" in prog:
        prog["max_seq_len"] = 128
    c["program"] = prog
    c["engine"] = dict(TINY_ENGINE)
    c["check"] = {"max_logit_gap": limit}
    return c


def make_root(tmp, configs: dict, traffic: dict, workloads: list,
              per_layer=None) -> str:
    """A checkout-shaped tree under ``tmp``; returns its ``bench`` dir."""
    bdir = os.path.join(tmp, "bench")
    os.makedirs(os.path.join(bdir, "configs"))
    os.makedirs(os.path.join(bdir, "traffic"))
    shutil.copytree(os.path.join(BENCH, "reference"),
                    os.path.join(bdir, "reference"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bdir, "metrics"))
    for name, c in configs.items():
        with open(os.path.join(bdir, "configs", f"{name}.json"), "w") as f:
            json.dump(c, f)
    for name, t in traffic.items():
        with open(os.path.join(bdir, "traffic", f"{name}.json"), "w") as f:
            json.dump(t, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2,
        "configs": [{"name": n, "source": c["source"],
                     "file": f"bench/configs/{n}.json", "reduced": [],
                     "why": "tiny"} for n, c in configs.items()],
        "workloads": workloads,
        # every tiny cell reports every end-to-end metric
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": per_layer if per_layer is not None
        else real["per_layer"],
    }
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bdir


@pytest.fixture
def tiny_opt(tmp_path):
    """A root with one tiny OPT cell; returns (bench dict, bench dir)."""
    bdir = make_root(str(tmp_path), {"opt-6.7b-bcq3": tiny_config(
        "opt-6.7b-bcq3")}, {"tiny": TINY_TRAFFIC},
        [{"name": "opt.tiny", "config": "opt-6.7b-bcq3", "traffic": "tiny",
          "chips": 1, "why": "tiny"}])
    with open(os.path.join(str(tmp_path), "BENCHMARK.json")) as f:
        return json.load(f), bdir
