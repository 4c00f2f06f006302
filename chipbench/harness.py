"""What every cell shares: the manifest, the files a cell is made of, the
device and its peaks, the compile cache, and the result line.

A cell is found by name alone.  ``BENCHMARK.json`` names its
configuration and traffic; the configuration's file is the one the
manifest gives, and names its model family (``"reference"``), whose
plain reference is ``reference/<family>.py``; the traffic mix is
``traffic/<traffic>.json``, the loop that drives it is
``drivers/<driver>.py`` (the traffic file names the driver), the limits
its outputs are judged by are ``limits/<workload>.json``, and each
per-layer metric is read by ``metrics/<metric>.py``.  Adding a cell, a
mix, a model family, a driver or a metric is adding files and manifest
entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from types import ModuleType
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CACHE_DIR = os.path.join(HERE, "_jax_cache")


class CellError(RuntimeError):
    """The cell cannot be measured here (no manifest entry, a missing
    file, no accelerator, an unknown device)."""


def load_module(path: str) -> ModuleType:
    if not os.path.isfile(path):
        raise CellError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + os.path.basename(path).replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> Dict:
    if not os.path.isfile(path):
        raise CellError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, manifest_path: str = MANIFEST,
            root: str = ROOT) -> Dict:
    """Everything one cell is made of, found by name."""
    man = read_json(manifest_path)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in {manifest_path}; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in man["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    centry = configs[w["config"]]
    bench = os.path.join(root, "chipbench")
    traffic = read_json(os.path.join(bench, "traffic",
                                     w["traffic"] + ".json"))
    per_layer = [m for m in man["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in man["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    config = read_json(os.path.join(root, centry["file"]))
    return {
        "workload": w,
        "config": config,
        "reference": os.path.join(bench, "reference",
                                  config["reference"] + ".py"),
        "traffic": traffic,
        "driver": os.path.join(bench, "drivers", traffic["driver"] + ".py"),
        "limits": read_json(os.path.join(bench, "limits",
                                         workload + ".json")),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
        "readers": {m["name"]: os.path.join(bench, "metrics",
                                            m["name"] + ".py")
                    for m in per_layer},
    }


def peaks(device_kind: str) -> Dict:
    table = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


def require_accelerator(jax, chips: int) -> List:
    """The devices to measure on; raises unless JAX sees at least
    ``chips`` TPU devices."""
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        seen = devs[0].platform if devs else "no"
        raise CellError(f"no TPU: JAX sees {seen} devices; this benchmark "
                        "measures only on the chip")
    if len(devs) < chips:
        raise CellError(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache(jax) -> None:
    """The persistent compile cache at a fixed path inside the checkout,
    whatever ``JAX_COMPILATION_CACHE_DIR`` says: every program is kept,
    so only a cell's first run in a checkout compiles.  Its size is not
    bounded, whatever ``JAX_COMPILATION_CACHE_MAX_SIZE`` says: a float32
    reference that unrolls every layer of every block of rows is a
    program of hundreds of MiB, which a bound would refuse to keep, and
    it would compile anew in every run."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_record(devs, peak_bytes: int) -> Dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``.  A backend that
    keeps no statistics (the CPU, where tests drive a cell) reads 0."""
    stats = [d.memory_stats() for d in devs]
    if any(s is None for s in stats):
        if devs[0].platform == "tpu":
            raise CellError("the TPU reports no memory statistics")
        return 0
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def judge(checks: Dict[str, Dict]) -> bool:
    """Every compared number is finite and at or below its limit."""
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def emit(result: Dict, checks: Dict[str, Dict]) -> None:
    """Print the compared numbers beside their limits as the last lines
    of standard error, and the result as the last line of standard
    output, with the checks under the key that comes last."""
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(dict(result, checks=checks)), flush=True)
