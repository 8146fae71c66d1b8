"""Readings the limits were set from, on the chip.  The benchmark's own
runs never call this.

    python3 benchmarks/chip/calibrate.py train --workload d20-train-diloco \
        --seeds 1,2,3 --control-seeds 1,2,3
    python3 benchmarks/chip/calibrate.py describe --trace-dir <dir>

``train``: per seed, the program's numbers from its first round
(``train.start``, the set-up of a run) against the reference (the lower
readings); on ``--control-seeds``, the precision control (the reference
with fp8 matmul operands, put in the program's place) and the planted
faults (``half_batch``, ``no_outer``; a state left unchanged reads 1 by
the measure and needs no run).  Each line printed is one JSON reading;
with ``--out`` they are also written to that file.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import harness

FAULTS = ("half_batch", "no_outer")


def _emit(rec, out):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def train_readings(cell, seeds, control_seeds, out):
    import train

    conf, t = cell.config, cell.traffic
    m, B, S = conf["run_as"], conf["train"]["batch"], conf["train"]["seq"]
    for seed in sorted(set(seeds) | set(control_seeds)):
        t0 = time.perf_counter()
        ref = train.follow_reference(m, t, B, S, seed)
        rec = {"seed": seed, "reference_s": time.perf_counter() - t0}
        if seed in seeds:
            t0 = time.perf_counter()
            _, state, _, prog = train.start(cell, seed)
            del state
            gc.collect()
            rec["program"] = train.compare(prog, ref)
            rec["program_loss"] = prog["loss"]
            rec["program_s"] = time.perf_counter() - t0
        if seed in control_seeds:
            rec["control"] = train.compare(
                train.follow_reference(m, t, B, S, seed, dtype="fp8"), ref)
            for fault in FAULTS:
                rec[fault] = train.compare(
                    train.follow_reference(m, t, B, S, seed, fault=fault),
                    ref)
        rec["reference_loss"] = ref["loss"]
        _emit(rec, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("train", "describe"))
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--trace-dir")
    ap.add_argument("--traffic", default="",
                    help="key=value,... laid over the traffic file")
    ap.add_argument("--program", default="",
                    help="key=value,... laid over the config's program "
                         "settings (e.g. compute_dtype=\"bfloat16\")")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    if args.mode == "describe":
        import trace
        path = trace.find_xplane(args.trace_dir)
        for line in trace.describe(path):
            _emit({"trace": line}, args.out)
        return 0
    root = harness.checkout_root()
    cell = harness.load_cell(args.workload, root)
    kv = lambda s: {k: json.loads(v) for k, v in
                    (x.split("=") for x in s.split(",") if x)}
    cell.traffic = dict(cell.traffic, **kv(args.traffic))
    cell.config = dict(cell.config, program=dict(
        cell.config.get("program", {}), **kv(args.program)))
    harness.use_cache(root)
    harness.import_program(root)
    harness.device_check(cell.chips)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    train_readings(cell, ints(args.seeds), ints(args.control_seeds),
                   args.out)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.Refused as e:
        print(f"calibrate.py: refused: {e}", file=sys.stderr)
        sys.exit(2)
