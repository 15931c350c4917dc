"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data that this file finds by name:
`BENCHMARK.json` gives the cell's configuration and traffic;
`benchmark/configs/<configuration>.json` the sizes and how the trainer is
started; `benchmark/traffic/<traffic>.json` the mix and the driver
(`benchmark/drivers/<driver>.py`) that plays it; and with `--trace 1`
every per-layer metric the cell lists is read by
`benchmark/layer_metrics/<metric>.py`. No cell, configuration or metric
is named in this file or in a driver.

This process never touches JAX's devices: the children it starts hold
the chip, one at a time. The last line of stdout is the result; a run
that finds no TPU prints none and exits 3. A run that fails (exit 1)
leaves its logs in `.bench_failed/<cell>.<seed>/` (`harness/failed.py`)
and says so in its last lines; any other end leaves nothing.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import failed, procs  # noqa: E402
from benchmark.harness.cell import Cell  # noqa: E402


def load(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default="", help="a tiny configuration "
                    "file to walk the cell's control flow with on the CPU; "
                    "such a run prints no result and exits 3")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "edl_tpu")):
        print("the program (edl_tpu/) is not in this checkout",
              file=sys.stderr)
        return 2
    bench = load("BENCHMARK.json")
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        print(f"no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    config_path = args.rehearse or next(
        c["file"] for c in bench["configs"] if c["name"] == entry["config"])
    config_path = os.path.join(ROOT, config_path)
    peaks = load("benchmark", "peaks.json")
    work = os.path.join(ROOT, ".bench_work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    # whatever ran before on this host writes nothing back to disk while
    # this run measures; `finally` below does the same for the next run
    os.sync()
    cell = Cell(root=ROOT, work=work, name=args.workload,
                chips=entry["chips"], config=load(config_path),
                config_path=config_path,
                traffic=load("benchmark", "traffic",
                             entry["traffic"] + ".json"),
                seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace), rehearse=bool(args.rehearse), t0=T0)
    driver = importlib.import_module(
        "benchmark.drivers." + cell.traffic["driver"])
    try:
        out = driver.run(cell)
        device = out.pop("device")
        procs.say(f"correct={out['correct']} attempted={out['attempted']} "
                  f"failed={out['failed']} {out['values']} {device}")
        kind = device["kind"]
        if cell.rehearse or device["platform"] != "tpu":
            raise procs.Refused(f"no TPU: refused (ran on "
                                f"{device['platform']} {kind!r})")
        if kind not in peaks:
            raise procs.Refused(f"no TPU: refused ({kind!r} is not in "
                                "benchmark/peaks.json)")
        evidence = {**out["evidence"], "peak": peaks[kind],
                    "device": device}
        line = {"correct": out["correct"], "attempted": out["attempted"],
                "failed": out["failed"], "metrics": {},
                "device": {"platform": device["platform"], "kind": kind,
                           "count": device["count"], "memory_peak_bytes":
                               device["memory_peak_bytes"]}}
        if cell.trace:
            from benchmark.reduce import xplane
            trace = xplane.reduce_dir(cell.trace_dir, device["count"])
            evidence["trace"] = trace
            line["device"].update(busy_s=trace["busy_s"],
                                  window_s=trace["window_s"])
            line["breakdown"] = xplane.breakdown(trace)
            wanted = bench["per_layer"]
        else:
            wanted = bench["end_to_end"]
        for metric in wanted:
            if not applies(metric, cell.name):
                continue
            if cell.trace:
                reader = importlib.import_module(
                    "benchmark.layer_metrics." + metric["name"])
                value = reader.read(cell, evidence)
            else:
                value = out["values"].get(metric["name"])
            if value is not None:
                line["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        print(json.dumps(line), flush=True)
        return 0
    except procs.Refused as e:
        print(str(e), file=sys.stderr)
        return 3
    except procs.BenchFailure as e:
        print(f"failed: {e}", file=sys.stderr)
        procs.stop_all()  # the logs are whole before they are copied
        kept = failed.keep(work, ROOT, args.workload, args.seed)
        print("the launcher's last lines:\n"
              f"{failed.tail(kept, 'launcher.log')}\n"
              f"this run's logs are kept in {kept}", file=sys.stderr)
        return 1
    finally:
        procs.stop_all()
        shutil.rmtree(work, ignore_errors=True)
        os.sync()


if __name__ == "__main__":
    sys.exit(main())
