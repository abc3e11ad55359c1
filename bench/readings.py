#!/usr/bin/env python3
"""Readings of the numbers that decide `correct`, over many seeds, on the
chip: the program's and the control's, from which a cell's limits are set.

    python3 bench/readings.py WORKLOAD --seeds 101,102,... --control 3 \
        --seconds 5 --out DIR

One process builds the cell's entry for each seed in turn (so set-up
compiles once), drives a short window at the cell's own load, compares what
the window produced with the reference and, for the first `--control`
seeds, compares the control (the reference in three bfloat16 passes put in
the program's place) too. Prints one JSON line per seed and appends it to
`DIR/<workload>.jsonl`. The benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import cell


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    _, wl, cfg, traffic, _ = cell.load_cell(args.workload)
    devices, rules = cell.open_chip(cell.ROOT, int(wl["chips"]))
    import reference
    ref = reference.Reference(cfg["geometry"])
    os.makedirs(args.out, exist_ok=True)
    sink = open(os.path.join(args.out, args.workload + ".jsonl"), "a")
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        entry = cell.Archive(cfg, traffic, seed, rules, len(devices))
        entry.warm()
        t1 = time.perf_counter()
        gcm = cell.GcMeter()
        rec = entry.window(args.seconds, cell.SAMPLE_LONG)
        gcm.close()
        t2 = time.perf_counter()
        row = {"seed": seed, "program": cell.compare(rec["sample"],
                                                     entry.inputs, ref),
               "ref_s": time.perf_counter() - t2, "setup_s": t1 - t0,
               "attempted": rec["attempted"],
               "kept": rec["kept"], "chunks": rec["chunks"],
               "gc": [gcm.count, gcm.gen2, gcm.longest],
               "audio_rate": rec["audio_s"] / rec["seconds"]}
        if n < args.control:
            t3 = time.perf_counter()
            row["control"] = cell.compare(
                cell.control(rec["sample"], entry.inputs, cfg["geometry"]),
                entry.inputs, ref)
            row["control_s"] = time.perf_counter() - t3
        line = json.dumps(row)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()
        del entry, rec
        gc.collect()
    print("peak bytes", [(d.memory_stats() or {}).get("peak_bytes_in_use")
                         for d in devices], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
