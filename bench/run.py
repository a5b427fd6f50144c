#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

`--trace 0` measures the cell's end-to-end metrics with the profiler
off; `--trace 1` traces a window of its own and reports the cell's
per-layer metrics. Every run then checks what the timed path produced
against the plain reference. The last line of standard output is one
JSON object: correct, attempted, failed, metrics, device (and with
`--trace 1` the breakdown), then the numbers compared with their
limits. Without a TPU, or with fewer chips than the cell asks for, the
run prints no result and exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb here")
    ap.add_argument("--sweep", default=None, metavar="RPS,RPS,...",
                    help="serving cells: offer each rate for --seconds in "
                         "one process and print p50/p95 and the completed "
                         "rate of each, instead of a run")
    ap.add_argument("--control", action="store_true",
                    help="run the cell's control (the reference in the "
                         "next lower precision) in the program's place, "
                         "instead of the program")
    ap.add_argument("--fault", default="",
                    help="with --control: the reference with this fault "
                         "planted (half_batch, value_zero) instead")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    try:
        bench = harness.benchmark()
        cell = harness.cell(args.workload, bench)
        import jax
        harness.enable_compile_cache()
        devs = harness.devices(cell["workload"]["chips"])
        peaks = harness.peaks(devs[0].device_kind)
    except harness.Refused as e:
        sys.stderr.write(f"refused: {e}\n")
        return 3
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}",
          flush=True)
    from bench import trace_reduce
    seed = harness.seed31(args.seed)
    spans = harness.Spans()
    if args.control:
        out = (cell["code"].control_run(cell, seed, args.fault)
               if args.fault else cell["code"].control_run(cell, seed))
        print(json.dumps(out))
        return 0
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell['traffic']['driver']}.py")
    if args.sweep:
        driver.sweep(cell, seed, [float(r) for r in args.sweep.split(",")],
                     args.seconds)
        return 0

    def trace_body(body):
        spans.tracing = True
        try:
            return harness.traced(
                body, lambda p: trace_reduce.reduce(
                    p, len(devs), cell["code"].KERNEL_PATTERNS),
                args.keep_trace)
        finally:
            spans.tracing = False

    out = driver.run(cell, args, seed, devs, spans, T_START, trace_body)
    e2e = {m["name"]: m for m in harness.end_to_end_for(args.workload, bench)}
    metrics = {}
    if args.trace:
        ctx = dict(out, spans=spans.records, peaks=peaks, chips=len(devs),
                   sizes=cell["sizes"], traffic=cell["traffic"])
        for m in harness.per_layer_for(args.workload, bench):
            value = harness.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["device"]["busy_s"] = out["reduced"]["busy_s"]
        out["device"]["window_s"] = out["reduced"]["window_s"]
    else:
        for name, value in dict(out["metrics"], setup_s=out["setup_s"]).items():
            if name in e2e:
                metrics[name] = {"value": value, "unit": e2e[name]["unit"]}
    checks = harness.report_checks(out["checks"])
    result = {"correct": harness.checks_pass(out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": out["device"]}
    if args.trace:
        result["breakdown"] = trace_reduce.breakdown(out["reduced"])
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
