"""Smoke tests for the dry-run path itself on a small fake-device mesh.

Each runs `build_cell` + lower + compile in a subprocess (fresh jax, 16
fake devices standing in for the 512-device production run) with REDUCED
configs patched in — guards the launch/dryrun plumbing against
regressions without the cost of full-size lowering.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_cell_sub(arch: str, shape: str, extra: str = "") -> dict:
    prog = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax
    import repro.launch.mesh as mesh_mod
    # shrink the production mesh to the test device count
    mesh_mod.make_production_mesh = lambda multi_pod=False: mesh_mod.make_mesh(
        (2, 2, 4) if multi_pod else (4, 4),
        ("pod", "data", "model") if multi_pod else ("data", "model"))
    import repro.launch.dryrun as dr
    import repro.configs.base as base
    from repro.configs import get_reduced
    real_get = dr.__dict__  # noqa
    import repro.configs as cfgs
    orig = cfgs.get_config
    def reduced_cfg(a):
        c = get_reduced(a)
        return c.replace(scan_layers=True, max_seq_len=4096)
    import repro.launch.dryrun
    repro.launch.dryrun.__dict__["build_cell"].__globals__["get_config"] = reduced_cfg
    # shrink shapes
    from repro.configs.base import SHAPES, ShapeCfg
    SHAPES["train_4k"] = ShapeCfg("train_4k", 64, 8, "train")
    SHAPES["prefill_32k"] = ShapeCfg("prefill_32k", 128, 4, "prefill")
    SHAPES["decode_32k"] = ShapeCfg("decode_32k", 128, 8, "decode")
    SHAPES["long_500k"] = ShapeCfg("long_500k", 256, 4, "decode")
    res = dr.run_cell("{arch}", "{shape}", roofline=False {extra})
    print("RESULT" + json.dumps({{"ok": bool(res["compile_ok"]),
                                  "mem": res["device_mem_gb"]}}))
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=560,
                       env={**os.environ, "PYTHONPATH": SRC,
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2500:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("arch,shape", [
    ("stablelm_1_6b", "train_4k"),
    ("mixtral_8x7b", "decode_32k"),
    ("mamba2_2_7b", "long_500k"),
    ("whisper_medium", "prefill_32k"),
])
def test_dryrun_cell_compiles(arch, shape):
    out = run_cell_sub(arch, shape)
    assert out["ok"]


def test_dryrun_multi_pod():
    out = run_cell_sub("stablelm_1_6b", "train_4k", extra=", multi_pod=True")
    assert out["ok"]


def test_dryrun_skips_long_context_for_full_attention():
    with pytest.raises(AssertionError) as e:
        run_cell_sub("phi4_mini_3_8b", "long_500k")
    assert "SKIP" in str(e.value)
