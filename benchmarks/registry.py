"""Finds the benchmark's parts by the names `BENCHMARK.json` uses, so that a
later PR adds a cell, a configuration, a traffic mix, a generator, a
reference or a per-layer metric as files and manifest entries alone.

    configs/<name>.json      traffic/<name>.json     workloads/<cell>.json
    generators/<name>.py     references/<name>.py
    end_to_end/<name>.py     layer_metrics/<name>.py

Imports nothing of the program and nothing of jax: producer processes load
their generator through it.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(CHECKOUT, "BENCHMARK.json")


class BenchmarkError(Exception):
    """The run cannot go on (as opposed to a run that finds wrong results)."""


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise BenchmarkError(f"no {kind}/{name}.json in {BENCH_DIR}") from None


def load_module(kind: str, name: str):
    """`<kind>/<name>.py` as a module; names may hold dots."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no {kind}/{name}.py in {BENCH_DIR}")
    mod_name = f"benchmarks_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    with open(MANIFEST) as fh:
        return json.load(fh)


def cell(name: str) -> dict:
    """Everything one cell runs: its manifest entry, its own file, its
    configuration and its traffic mix, and the metrics it reports."""
    man = manifest()
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"BENCHMARK.json has no workload {name!r}; it has "
            f"{[w['name'] for w in man['workloads']]}")
    own = load_json("workloads", name)
    for key in ("config", "traffic"):
        if own[key] != entry[key]:
            raise BenchmarkError(
                f"workloads/{name}.json says {key}={own[key]!r}, "
                f"BENCHMARK.json says {entry[key]!r}")

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "name": name, "chips": entry["chips"], "own": own,
        "config": load_json("configs", own["config"]),
        "traffic": load_json("traffic", own["traffic"]),
        "end_to_end": [m for m in man["end_to_end"] if reported(m)],
        "per_layer": [m for m in man["per_layer"] if reported(m)],
        # every metric the manifest names: the detail line shows them all
        "manifest_metrics": {k: man[k] for k in ("end_to_end", "per_layer")},
    }


def stream_plans(config: dict, traffic: dict, rehearse: bool) -> list:
    """One plan per input stream, in the configuration's order: what is
    sent (the configuration's `inputs`: generator and the data's own
    parameters: keys, skew, value ranges) and how (the traffic mix:
    producers, pool, rows per frame). A mix may override a generator
    parameter for every stream (`params`) or anything for one stream
    (`streams.<name>`)."""
    rows_key = "rehearse_rows_per_frame" if rehearse else "rows_per_frame"
    plans = []
    for stream, data in config["inputs"].items():
        own = traffic.get("streams", {}).get(stream, {})
        params = {**data["params"],
                  **(data.get("rehearse_params", {}) if rehearse else {}),
                  **traffic.get("params", {}), **own.get("params", {})}
        plan = {"stream": stream, "generator": data["generator"],
                "producers": own.get("producers", traffic["producers"]),
                "pool": own.get("pool", traffic["pool"]),
                "rows": own.get(rows_key, traffic[rows_key])}
        plan["params"] = {**params, "rows_per_frame": plan["rows"]}
        plans.append(plan)
    return plans
