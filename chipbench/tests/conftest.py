"""The benchmark's own tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

They patch out the harness's look for a chip where they drive a run."""
import json
import pathlib
import shutil
import sys

import jax
import pytest

jax.config.update("jax_platform_name", "cpu")
# the CPU runs' compiled programs are not kept in the checkout
jax.config.update("jax_enable_compilation_cache", False)
REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

TEST_PEAKS = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
              "hbm_bytes_per_s": 819e9}


@pytest.fixture
def small_copy(tmp_path):
    """A copy of the benchmark with 32x32 inputs and a test benchmark of
    its own: ``<config>.closed`` cells (8 clients, one bucket of 4) and
    ``resnet50-224.open`` (40 arrivals/s, buckets 2 and 4), each held to
    its configuration's closed-cell limits."""
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    root = tmp_path / "chipbench"
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    for path in (root / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["in_shape"] = [32, 32, 3]
        path.write_text(json.dumps(cfg))
    serving = {"max_batch": 4, "max_delay_ms": 2, "max_pending": 64}
    (root / "traffic" / "closed.json").write_text(json.dumps({
        "kind": "closed", "clients": 8, "pool": 8,
        "serving": dict(serving, buckets=[4])}))
    (root / "traffic" / "open.json").write_text(json.dumps({
        "kind": "open", "rate_per_s": 40, "pool": 8,
        "serving": dict(serving, buckets=[2, 4])}))
    cells = {"resnet50-224.closed": ("resnet50-224", "closed"),
             "mobilenetv1-224.closed": ("mobilenetv1-224", "closed"),
             "resnet50-224.open": ("resnet50-224", "open")}
    for name, (config, _) in cells.items():
        limits = json.loads(
            (root / "workloads" / f"{config}.closed.json").read_text())
        (root / "workloads" / f"{name}.json").write_text(json.dumps(limits))
    closed = [c for c, (_, t) in cells.items() if t == "closed"]
    metric = {"better": "lower", "source": "host_clock", "bound": 0.25}
    bench = {
        "configs": real["configs"],
        "workloads": [{"name": c, "config": cfg, "traffic": t, "chips": 1,
                       "why": "test"} for c, (cfg, t) in cells.items()],
        "end_to_end": [
            dict(metric, name="images_per_s", unit="images/s",
                 better="higher", workloads=closed),
            dict(metric, name="latency_p95_ms", unit="ms",
                 workloads=["resnet50-224.open"]),
            dict(metric, name="setup_s", unit="s")],
        "per_layer": [
            {"name": "batch_mean", "unit": "images", "better": "higher",
             "source": "program_counter", "layer": "serving",
             "moves": "latency_p95_ms"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def no_chip_check(monkeypatch):
    """Skip the harness's look for a chip: the run proceeds on the CPU."""
    import repro.kernels.common as kernels_common

    from chipbench import env

    monkeypatch.setattr(env, "device_check", lambda devices, chips: {
        "platform": "tpu", "kind": "test", "count": 1, "peaks": TEST_PEAKS})
    monkeypatch.setattr(kernels_common, "interpret_mode", lambda: False)


def run_cell(root, capsys, *argv):
    """``chipbench.run.main`` on ``root``; returns (exit code, last stdout
    line as JSON or None, stderr)."""
    from chipbench import run

    rc = run.main(list(argv), root=root)
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return rc, last, err
