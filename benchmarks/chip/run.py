"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout that holds ``BENCHMARK.json``, the program
under ``src/`` and this directory.  It refuses any backend but a TPU, and
fewer chips than the cell asks for: it then exits non-zero and prints no
result.  Otherwise the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number the correctness comparison made, beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict  # noqa: E402

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(cell, seed: int, seconds: float, trace: bool, root,
            device: Dict, t_start: float) -> Dict:
    """One run of ``cell`` on whatever backend JAX has: the result line's
    object, ``checks`` last."""
    driver = importlib.import_module(cell.traffic["driver"])
    trace_dir = None
    if trace:
        trace_dir = str(root / ".bench_traces" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    res = driver.run(cell, seed, seconds, trace_dir, t_start)

    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    checks = harness.judge(res["numbers"], cell.limits)
    out = {"correct": harness.all_within(checks),
           "attempted": res["attempted"], "failed": res["failed"]}
    if trace:
        import trace as trace_mod
        path = trace_mod.find_xplane(trace_dir)
        red = trace_mod.reduce_trace(path, window_s=res["trace_window_s"])
        res["trace"] = red
        res["peak_flops"] = harness.peak(device["kind"])
        out["metrics"] = harness.read_per_layer(cell, res)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["top_ops"],
                            "idle_gaps": red["idle_gaps"]}
    else:
        out["metrics"] = {m["name"]: {"value": float(res[m["name"]]),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = device
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    root = harness.checkout_root()
    cell = harness.load_cell(args.workload, root)
    harness.use_cache(root)
    harness.import_program(root)
    device = harness.device_check(cell.chips)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    harness.emit(measure(cell, args.seed, args.seconds, bool(args.trace),
                         root, device, T_START))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.Refused as e:
        print(f"run.py: refused: {e}", file=sys.stderr)
        sys.exit(2)
