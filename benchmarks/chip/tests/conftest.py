import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[3]
for p in (ROOT / "src", ROOT / "benchmarks"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


DATA = pathlib.Path(__file__).resolve().parent / "data"


def tiny_cell(config: str = "tiny"):
    """A cell of the benchmark's own harness at a size a CPU test holds."""
    from chip import harness

    bench = harness.load_json(ROOT / "BENCHMARK.json")
    name = f"{config}.tiny"
    bench = dict(bench, workloads=[
        {"name": name, "config": config, "traffic": "tiny", "chips": 1}])
    bench["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                          for m in bench["per_layer"]]
    return harness.load_cell(bench, name, DATA)


def run_tiny(seed: int, *, config: str = "tiny", seconds: float = 1e-3,
             trace: bool = False, fault=None) -> dict:
    """One run of the tiny cell on the CPU, the look for a chip skipped. A
    window this short runs one wave, which the drain finishes, so the check
    compares all of its requests."""
    import time

    from chip import harness

    return harness.run_cell(tiny_cell(config), seed, seconds, trace,
                            t_start=time.perf_counter(), require_chip=False,
                            fault=fault)
