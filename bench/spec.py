"""Find everything a cell needs by the names ``BENCHMARK.json`` gives.

    bench/configs/<config>.json     the configuration as it is run
    bench/reference/<ref>.py        its plain reference (named in the file)
    bench/traffic/<traffic>.json    the traffic mix's parameters
    bench/metrics/<metric>.py       one reader per per-layer metric

A later change adds a configuration, a mix or a metric by adding files
and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# what every per-layer reader declares, checked against BENCHMARK.json
READER_FIELDS = ("UNIT", "BETTER", "SOURCE", "LAYER", "MOVES")


class SpecError(RuntimeError):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r}; known: "
                    f"{sorted(e['name'] for e in entries)}")


def workload(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def _json(path: str) -> dict:
    if not os.path.exists(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def config(bench: dict, name: str, bench_dir: str = BENCH_DIR) -> dict:
    entry = _by_name(bench["configs"], name, "configuration")
    cfg = _json(os.path.join(os.path.dirname(bench_dir), entry["file"]))
    if cfg.get("name") != name:
        raise SpecError(f"{entry['file']} names {cfg.get('name')!r}, "
                        f"not {name!r}")
    return cfg


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def load_module(path: str, modname: str):
    """Import a file by path (metric files carry dots in their names)."""
    if not os.path.exists(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def reference(name: str, bench_dir: str = BENCH_DIR):
    return load_module(os.path.join(bench_dir, "reference", f"{name}.py"),
                       f"bench_reference_{name}")


def end_to_end(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(bench: dict, cell: str) -> List[dict]:
    """Per-layer metrics this cell reports: those that list it, and those
    without a list whose moved metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def reader(entry: dict, bench_dir: str = BENCH_DIR):
    """The reader module of a per-layer metric, checked against its
    BENCHMARK.json entry."""
    name = entry["name"]
    mod = load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                      "bench_metric_" + name.replace(".", "_"))
    for field in READER_FIELDS:
        want = entry[field.lower()]
        got = getattr(mod, field, None)
        if got != want:
            raise SpecError(f"metric {name}: its file says {field}={got!r}, "
                            f"BENCHMARK.json says {want!r}")
    return mod

