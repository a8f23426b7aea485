"""Shared set-up of the benchmark's own tests: the benchmark's directory on
the import path, and its cells' configurations and traffic at sizes a CPU
test can hold.  Nothing here describes a chip or touches one."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "perfbench"
DATA = Path(__file__).resolve().parent / "data"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def bench_json() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_files(workload: str):
    """(configuration, traffic) of a cell of `BENCHMARK.json`, as files."""
    bench = bench_json()
    cell = {c["name"]: c for c in bench["workloads"]}[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cfg, traffic


def tiny(workload: str = "chatglm3-6b.train-s2k.1chip", **over) -> dict:
    """The cell's configuration at widths a CPU test can hold, as its
    architecture module (`yardstick/arch/<arch>.py`) shrinks it."""
    from yardstick import arch

    cfg, _ = cell_files(workload)
    return dict(arch.module(cfg).tiny(cfg), **over)


def tiny_traffic(workload: str = "chatglm3-6b.train-s2k.1chip", **over) -> dict:
    _, t = cell_files(workload)
    t.update(seq=64, v_eff=256)
    t.update(over)
    return t
