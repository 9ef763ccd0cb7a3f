"""Cells, configurations, traffic and metrics are found by name."""
import json
import shutil

import pytest

from chipbench import spec
from chipbench.spec import HERE, REPO


def test_every_cell_of_the_benchmark_loads():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.chips == w["chips"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.load_module(HERE, "metrics", m["name"]).read)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        spec.load_module(HERE, "refs", cell.config["reference"])
        spec.load_module(HERE, "systems", cell.config["system"])


def _snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for p in root.rglob("*") if p.is_file()}


def test_a_cell_added_as_new_files_is_found(tmp_path):
    """A new configuration, traffic mix, cell and metrics, added as new
    files and new BENCHMARK.json entries, load without an edit to any
    existing file."""
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    root = tmp_path / "chipbench"
    before = _snapshot(root)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "configs" / "resnet50-224.json").read_text())
    cfg["in_shape"] = [160, 160, 3]
    (root / "configs" / "resnet50-160.json").write_text(json.dumps(cfg))
    (root / "traffic" / "burst.json").write_text(json.dumps({
        "kind": "open", "rate_per_s": 50, "pool": 16,
        "serving": {"max_batch": 8, "buckets": [8], "max_delay_ms": 3}}))
    (root / "workloads" / "resnet50-160.burst.json").write_text(json.dumps(
        {"limits": {"unanswered": 0, "logit_err_max": 0.5,
                    "logit_err_rms": 0.2}}))
    (root / "metrics" / "latency_p99_ms.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    (root / "metrics" / "queue.depth_p50.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "resnet50-160", "source": "x",
                             "file": "chipbench/configs/resnet50-160.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "resnet50-160.burst",
                               "config": "resnet50-160", "traffic": "burst",
                               "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "latency_p99_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["resnet50-160.burst"]})
    bench["per_layer"].append({"name": "queue.depth_p50", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "serving", "moves": "latency_p99_ms"})
    bench["per_layer"].append({"name": "mfu.all", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "model step",
                               "moves": "images_per_s"})

    cell = spec.load_cell("resnet50-160.burst", bench, root=root)
    assert cell.config["in_shape"] == [160, 160, 3]
    assert cell.traffic["rate_per_s"] == 50
    assert cell.limits["logit_err_max"] == 0.5
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "latency_p99_ms"]
    assert [m["name"] for m in cell.per_layer] == ["queue.depth_p50"]
    for m in cell.end_to_end[1:] + cell.per_layer:
        assert spec.load_module(root, "metrics", m["name"]).read(None) > 0
    # a metric without a workloads key joins every cell that reports its
    # end-to-end metric, the old ones too
    old = spec.load_cell("mobilenetv1-224.closed", bench, root=root)
    assert "mfu.all" in [m["name"] for m in old.per_layer]
    after = _snapshot(root)
    assert all(after[p] == before[p] for p in before)


@pytest.mark.parametrize("bad", ["../x", "a/b", "", "x y", ".hidden"])
def test_names_that_would_leave_the_directory_are_refused(bad):
    bench = {"workloads": [{"name": "c", "config": bad, "traffic": "closed",
                            "chips": 1}], "end_to_end": [], "per_layer": []}
    with pytest.raises(spec.SpecError):
        spec.load_cell("c", bench)
