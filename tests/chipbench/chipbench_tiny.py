"""A benchmark tree at toy widths for the CPU tests: the real ``chipbench/``
data files copied beside a manifest of tiny cells, so that ``run.main`` runs
end to end on the virtual CPU devices with no chip."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# the toy cell's own readings (tests/chipbench, CPU): sound runs read up to 0.0043 on
# the gradient norm and the float8 control 0.0081 or more
TINY_LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 0.006, "update_norm_gap": 0.6}


def tiny_config(name: str = "tiny") -> dict:
    real = json.loads((ROOT / "chipbench/configs/gpt2-small.json").read_text())
    real.update(
        name=name, n_layer=2, n_head=2, d_model=64, max_seq=64, vocab_size=512,
        attention="xla", limits=dict(TINY_LIMITS),
        reduced=["n_layer", "n_head", "d_model", "max_seq", "vocab_size", "attention"],
    )
    return real


def tiny_mix(batch_per_chip: int = 4) -> dict:
    return {
        "runner": "train", "seq_len": 64,
        "batch_per_chip": batch_per_chip, "corpus_rows": 64, "branching": 4,
        "prefetch": 2, "steps_per_sample": 1,
    }


def make_tree(tmp: Path, cells=(("tiny-w1", 1), ("tiny-w4", 4))) -> Path:
    """``tmp/BENCHMARK.json`` + ``tmp/chipbench`` (data files of the real
    one, plus the tiny configuration and mix).  Returns ``tmp``."""
    shutil.copytree(
        ROOT / "chipbench", tmp / "chipbench",
        ignore=shutil.ignore_patterns("__pycache__", "fixtures"),
    )
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "chipbench/configs/tiny.json").write_text(json.dumps(tiny_config()))
    (tmp / "chipbench/traffic/tiny-b4.json").write_text(json.dumps(tiny_mix()))
    manifest["configs"] = [{
        "name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
        "reduced": tiny_config()["reduced"], "why": "toy widths for the CPU tests",
    }]
    manifest["workloads"] = [
        {"name": n, "config": "tiny", "traffic": "tiny-b4", "chips": c, "why": "test"}
        for n, c in cells
    ]
    for m in manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [n for n, _ in cells]
    (tmp / "BENCHMARK.json").write_text(json.dumps(manifest))
    return tmp


def run_cell(tree: Path, cell: str, capsys, seed: int = 7, seconds: float = 0.3, trace: int = 0):
    """``run.main`` without the look for a chip; returns (exit code, result
    line as a dict, all stdout)."""
    from chipbench import run

    code = run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        require_chip=False, root=tree, bench=tree / "chipbench",
    )
    out = capsys.readouterr().out
    return code, json.loads(out.strip().splitlines()[-1]), out
