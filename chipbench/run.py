#!/usr/bin/env python3
"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell, its configuration and the metrics in
``BENCHMARK.json``; the configuration's sizes in the file the manifest
names; the traffic mix in ``chipbench/traffic/<traffic>.json``, which names
its runner kind (``chipbench/runners/<runner>.py``); each per-layer metric's
reader in ``chipbench/metrics/<metric>.py``.  This file holds no list of its
own, so a later PR adds a cell, a configuration, a runner kind or a metric
as new files plus manifest entries and edits no file that is here.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any phase and prints no result.  The last line of stdout is
the result, one JSON object.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as this file can see it

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


@dataclass
class RunSpec:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    mix: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    t0: float
    out_dir: Path
    require_chip: bool
    say: Callable[[str], None]


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"chipbench: no {what} named {name!r} in BENCHMARK.json")


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def found_device() -> Dict[str, Any]:
    """The device as JAX reports it."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def require_tpu(chips: int) -> Dict[str, Any]:
    """The device, or exit non-zero: the benchmark has no CPU mode."""
    device = found_device()
    if device["platform"] != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX found {device}")
    if device["count"] < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX found {device}")
    return device


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at the fixed ``<checkout>/.jax_cache``; every program is kept, however
    quickly it compiled, so that a cell's second run compiles nothing."""
    import jax

    where = os.environ.get(CACHE_DIR_ENV) or str(root / ".jax_cache")
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def load_reader(name: str, metrics_dir: Path):
    path = metrics_dir / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"chipbench: per-layer metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(root: Path, bench: Path, name: str):
    """The manifest, and the named cell with its configuration and traffic
    mix, each from the file the manifest's names lead to."""
    from chipbench.traffic import generator

    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cell = by_name(manifest["workloads"], name, "workload")
    entry = by_name(manifest["configs"], cell["config"], "configuration")
    config = json.loads((root / entry["file"]).read_text())
    return manifest, cell, config, generator.load_mix(cell["traffic"], bench / "traffic")


def main(argv: Optional[List[str]] = None, require_chip: bool = True, root: Path = ROOT,
         bench: Path = BENCH) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    manifest, cell, config, mix = load_cell(root, bench, args.workload)
    device = require_tpu(int(cell["chips"])) if require_chip else found_device()
    # off the chip (the CPU tests) nothing is cached: XLA:CPU warns about
    # every program it reads back
    say(f"device {device}; compile cache at {enable_compile_cache(root) if require_chip else None}")

    runner = importlib.import_module(f"chipbench.runners.{mix['runner']}")
    out_dir = root / "chiprun_out" / "chipbench" / f"{cell['name']}.seed{args.seed}.trace{args.trace}"
    out_dir.mkdir(parents=True, exist_ok=True)
    result = runner.run(RunSpec(
        cell=cell, config=config, mix=mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t0=_T0, out_dir=out_dir, require_chip=require_chip, say=say,
    ))

    metrics: Dict[str, Any] = {}
    if args.trace:
        reported = {m["name"] for m in manifest["end_to_end"] if applies(m, cell["name"])}
        for m in manifest["per_layer"]:
            if not applies(m, cell["name"]) or m["moves"] not in reported:
                continue
            value = load_reader(m["name"], bench / "metrics").read(result["facts"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in manifest["end_to_end"]:
            if not applies(m, cell["name"]):
                continue
            if m["name"] not in result["end_to_end"]:
                raise SystemExit(f"chipbench: runner {mix['runner']!r} gave no {m['name']!r}")
            metrics[m["name"]] = {"value": float(result["end_to_end"][m["name"]]), "unit": m["unit"]}

    line = {
        "correct": result["correct"], "attempted": result["attempted"], "failed": result["failed"],
        "metrics": metrics, "device": dict(device, **result["device"]),
    }
    if result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    (out_dir / "result.json").write_text(json.dumps(line, indent=1))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
