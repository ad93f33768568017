"""DeepSeek-V2-Lite: its configuration file, and the served path against
its plain reference at tiny sizes on the CPU.

The configuration file is read as the harness reads it (the program's
format by ``jax.eval_shape``: no weight is drawn) and must give the
counts pinned by hand in ``test_bench_counts.py``.  The tiny cell keeps
every key of the real file and shrinks the widths to the program's
``reduced()`` sizes.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import counts, harness, spec, weights
from bench.reference.common import _dense_layer, stack_layers
from conftest import TINY_ENGINE, TINY_TRAFFIC, make_root

NAME = "deepseek-v2-lite-bcq3"


def _config():
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{NAME}.json")) as f:
        return json.load(f)


TINY = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=128, vocab_size=256,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, n_shared_experts=1,
            num_experts_per_tok=2, moe_intermediate_size=32)


def tiny_config(limit=1.0, scan=False):
    c = _config()
    c.update(TINY)
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=16)
    c["program"] = dict(c["program"], scan_layers=scan, max_seq_len=128,
                        rope_yarn_original_max_pos=16)
    c["quant"] = dict(c["quant"], group_size=16)
    c["engine"] = dict(TINY_ENGINE)
    c["check"] = {"max_logit_gap": limit}
    return c


def test_config_file_gives_the_pinned_counts():
    c = _config()
    cfg = harness.model_config(c)
    assert (cfg.n_layers, cfg.d_model, cfg.n_experts, cfg.experts_per_token,
            cfg.moe_d_ff, cfg.n_shared_experts, cfg.first_dense_layers,
            cfg.q_lora_rank, cfg.kv_lora_rank) == \
        (27, 2048, 64, 6, 1408, 2, 1, 0, 512)
    assert cfg.norm_topk_prob is False and cfg.routed_scaling_factor == 1.0
    rs = c["rope_scaling"]
    assert cfg.yarn == (rs["factor"], rs["original_max_position_embeddings"],
                        rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                        rs["mscale_all_dim"])
    from repro.models import Model
    abstract = weights.abstract_quantized(Model(cfg), cfg.quant)
    s = counts.shape_of(c, weights.head(abstract))
    assert s.kv_entry_bytes == 1_152                  # (512 + 64) x 2 B
    assert (s.head_gemm, s.vocab_rows, s.head_bytes) == \
        (True, 102_400, 104_857_600)                  # untied, 3-bit, once
    assert (s.layers, s.experts_per_token) == (27, 6)
    lins = {l.name: l for l in weights.linears(abstract)}
    for role in ("gate", "up", "down"):
        lin = lins[f"stack/scan/0/mlp/{role}"]
        assert (lin.experts, lin.layers, lin.stored_bytes) == \
            (64, 26, 1_441_792)
    # the cell's KV pool: 40,960 tokens of 27 layers x 1,152 B
    assert c["engine"]["kv_pool_tokens"] * 27 * s.kv_entry_bytes == \
        1_274_019_840


def _reference_logits(c, params, toks):
    ref = spec.reference(c["reference"])
    w = weights.plain(params)
    with jax.default_matmul_precision("highest"):
        x = ref.embed(w["embed"], jnp.asarray(toks), c)
        for g, i in stack_layers(w["stack"]):
            x = ref.block(_dense_layer(g, i), x, c, "f32")
        xn, head = ref.final({"embed": w["embed"],
                              "final_norm": w["final_norm"]}, x, c)
        return np.asarray(xn @ head.T)[..., : c["vocab_size"]]


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_served_path_matches_reference(scan):
    """Chunked prefill, then decode through the paged cache, against the
    reference's full forward over the same tokens."""
    from repro.models import Model
    from repro.serve import PagedServeEngine
    c = tiny_config(scan=scan)
    cfg = harness.model_config(c)
    model = Model(cfg)
    params = weights.draw(model, cfg.quant, 2**33 + 7)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, c["vocab_size"], 37).astype(np.int32)
    cont = rng.integers(0, c["vocab_size"], 6).astype(np.int32)
    eng = PagedServeEngine(model, params, num_blocks=32, block_size=8,
                           max_batch=4, max_seq_len=128,
                           prefill_buckets=(16, 32))
    got = eng.probe_logits(prompt, cont)            # [1 + 6, V]
    want = _reference_logits(c, params, np.concatenate([prompt, cont])[None])
    want = want[0, len(prompt) - 1:]
    scale = np.abs(want).max()
    # bf16 activations between layers against float32: a few percent of
    # the logits' range, as the dense cells' reference test allows (3%)
    assert np.abs(got - want).max() <= 0.03 * scale
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.85


def test_tiny_cell_is_correct_end_to_end(tmp_path):
    """The harness serves the tiny cell through the async frontend and
    its check passes against the reference."""
    bdir = make_root(str(tmp_path), {NAME: tiny_config(limit=0.5)},
                     {"tiny": TINY_TRAFFIC},
                     [{"name": "ds.tiny", "config": NAME, "traffic": "tiny",
                       "chips": 1, "why": "tiny"}])
    with open(os.path.join(str(tmp_path), "BENCHMARK.json")) as f:
        bench = json.load(f)
    import time
    out = harness.run_cell(bench, "ds.tiny", 2**31 + 17, 2.0, False,
                           time.perf_counter(), require_tpu=False,
                           bench_dir=bdir)
    assert out["correct"], out["check"]
    assert out["failed"] == 0


def test_traced_run_reads_the_routed_metrics(tmp_path, monkeypatch):
    """A traced run joins each step's counters to its span: the experts
    touched per MoE layer and the step's work (``step.mfu``,
    ``step.hbm_share``, which read nothing without the counter)."""
    from bench import peaks
    # the chip's table, so that readers run on the CPU; a CPU number is
    # never reported as a device metric (run.py refuses a non-TPU device)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    for m in per_layer:
        if "workloads" in m and "deepseek-v2-lite-bcq3.chat" in m["workloads"]:
            m["workloads"] = ["ds.tiny"]
    bdir = make_root(str(tmp_path), {NAME: tiny_config()},
                     {"tiny": TINY_TRAFFIC},
                     [{"name": "ds.tiny", "config": NAME, "traffic": "tiny",
                       "chips": 1, "why": "tiny"}], per_layer=per_layer)
    with open(os.path.join(str(tmp_path), "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = harness.run_cell(bench, "ds.tiny", 2**31 + 19, 2.0, True, 0.0,
                           require_tpu=False, bench_dir=bdir)
    got = out["metrics"]
    assert out["correct"] is True
    # 8 experts a layer at most, 2 a token at least
    assert 2 <= got["moe.experts_touched_mean"]["value"] <= 8
    assert got["step.mfu"]["value"] > 0 and got["step.hbm_share"]["value"] > 0


def test_routed_readers_by_hand():
    """``kernel.expert_gemm_roofline`` bounds each routed role's call on a
    step's real rows and touched experts and divides by the grouped
    kernel's device time alone; ``moe.experts_touched_mean`` is the mean
    counter over the MoE layers."""
    import types
    from bench.peaks import bound_s, peaks
    from repro.models import Model
    c = _config()
    cfg = harness.model_config(c)
    abstract = weights.abstract_quantized(Model(cfg), cfg.quant)
    lins = weights.linears(abstract)
    shape = counts.shape_of(c, weights.head(abstract))
    steps = [counts.Step("decode", [17, 33, 5],
                         counters={"experts_touched": 26 * 14}),
             counts.Step("decode", [18, 34, 6],
                         counters={"experts_touched": 26 * 16}),
             counts.Step("prefill", [0, 300], 512,
                         counters={"experts_touched": 26 * 60})]
    op = types.SimpleNamespace
    ops = [op(op="bcq_grouped_matmul_tiled.3", dur=0.004, start=0.0),
           op(op="bcq_grouped_matmul_tiled.4", dur=0.006, start=0.0),
           op(op="bcq_matmul_tiled.9", dur=1.0, start=0.0)]
    v5e = peaks("TPU v5 lite")
    ctx = op(linears=lins, shape=shape, peaks=v5e,
             in_window=lambda evs: evs,
             device=op(ops=lambda: ops),
             traced_steps=lambda: steps,
             steps=lambda lo, hi: steps, window=(0.0, 1.0),
             program_runs=lambda kind: [0] * {"decode": 4, "prefill": 1}[kind])
    roof = spec.load_module(
        os.path.join(spec.BENCH_DIR, "metrics",
                     "kernel.expert_gemm_roofline.py"), "t_expert_roof")
    touched = spec.load_module(
        os.path.join(spec.BENCH_DIR, "metrics",
                     "moe.experts_touched_mean.py"), "t_touched")
    # gate, up, down: 26 layers each, 1,441,792 B an expert's matrix
    def call(rows, per, n, k):
        a = rows * 6
        return bound_s(2.0 * a * n * k,
                       per * 1_441_792 + a * k * 2 + a * n * 2, v5e)

    def step(rows, per):
        return 26 * (2 * call(rows, per, 1408, 2048)
                     + call(rows, per, 2048, 1408))
    want = ((step(3, 14) + step(3, 16)) / 2 * 4 + step(300, 60)) / 0.010
    assert roof.read(ctx) == pytest.approx(100.0 * want, rel=1e-12)
    assert touched.read(ctx) == 15.0         # decode steps only
