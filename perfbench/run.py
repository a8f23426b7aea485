"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
`BENCHMARK.json`, its configuration in `configs/<config>.json`, its
traffic mix in `traffic/<traffic>.json` (whose `kind` names its module in
`drivers/`), the limits of its checks in `limits/<workload>.json`, and
each per-layer metric's reader in `metrics/<metric>.py`.

With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
window.  The last line of stdout is one JSON object; the numbers that
decide `correct` end stderr, each beside its limit, and close the JSON
line under `checks`.  Without a TPU, or with fewer chips than the cell
asks for, it exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]


class NoChip(SystemExit):
    pass


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_cell(name: str):
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, traffic


def metrics_of(bench: dict, cell: dict, trace: bool):
    """The end-to-end (trace 0) or per-layer (trace 1) metrics of a cell."""
    def has(m):
        return "workloads" not in m or cell["name"] in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if has(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"] if has(m) and m["moves"] in names]


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_devices(chips: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}")
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"need {chips} tpu device(s), found "
                     f"{len(devs)} {devs[0].platform}")
    return devs


def use_compile_cache():
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def result_line(bench, cell, out, trace: bool) -> dict:
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    for m in metrics_of(bench, cell, trace):
        if trace:
            value = reader(m["name"])(out["trace"])
            if value is None:
                continue
        else:
            value = out["e2e"][m["name"]]
        metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    dev = out["device"]
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": out["chips"],
                       "memory_peak_bytes": out["peak"]}}
    if trace:
        line["device"]["busy_s"] = out["busy_s"]
        line["device"]["window_s"] = out["window_s"]
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = find_cell(args.workload)
    devices = require_devices(cell["chips"])
    use_compile_cache()
    from yardstick import compare

    driver = importlib.import_module(f"drivers.{traffic['kind']}")
    limits = compare.load_limits(HERE, cell["name"])
    out = driver.run(cell, cfg, traffic, args, devices, T_START, limits)
    out["device"] = devices[0]
    line = result_line(bench, cell, out, bool(args.trace))
    for note in out.get("notes", []):
        print(note, file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    try:
        main()
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
