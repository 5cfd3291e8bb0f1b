"""A configuration, a traffic mix and a per-layer metric are files found by
name: adding one takes new files and entries, and edits no existing file."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402


def digest(root: Path) -> dict:
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_every_cell_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        spec.load_module("generators", cell.mix["generator"])
        spec.load_module("datasets", cell.config["dataset"]["generator"])
        for m in cell.per_layer:
            assert callable(spec.load_module("layer_metrics",
                                             m["name"]).read)


def test_a_new_mix_cell_and_metric_need_no_edit(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = digest(root)

    (root / "bench" / "traffic" / "amzn-get8-r10.json").write_text(
        json.dumps({"generator": "ops", "rate_rps": 10.0,
                    "ops": [{"kind": "find", "share": 1.0, "keys": 8,
                             "choose": "existing_uniform"}],
                    "warm": {"find": [128]}}))
    (root / "bench" / "layer_metrics" / "batches_seen.py").write_text(
        "def read(ctx):\n    return ctx['stats']['batches'] or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "amzn-get8-r10", "config": bench["configs"][0]["name"],
        "traffic": "amzn-get8-r10", "chips": 1, "why": "a test cell"})
    bench["per_layer"].append({
        "name": "batches_seen", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "batcher",
        "moves": "p50_ms", "workloads": ["amzn-get8-r10"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = digest(root)
    changed = [k for k in before if after.get(k) != before[k]]
    assert changed == ["BENCHMARK.json"]

    cell = spec.resolve("amzn-get8-r10", root)
    assert "batches_seen" in [m["name"] for m in cell.per_layer]
    reader = spec.load_module("layer_metrics", "batches_seen", root)
    assert reader.read({"stats": {"batches": 3}}) == 3
    gen = spec.load_module("generators", cell.mix["generator"], root)
    rng = np.random.default_rng(0)
    t = gen.arrivals(cell.mix["rate_rps"], 2.0, rng)
    plan = gen.plan(cell.mix, np.arange(100.0), rng, t.size, None)
    assert len(plan) == 20 and all(p.shape == (8,) for _, p in plan)
