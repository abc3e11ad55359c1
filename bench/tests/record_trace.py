#!/usr/bin/env python3
"""Record one of the small chip traces the trace-reduction tests read.

    python3 bench/tests/record_trace.py WORKLOAD BATCHES OUT.xplane.pb

On the chip: builds the archive cell's entry from a fixed seed, warms it,
then traces `BATCHES` batches of its bank through `Preprocessor.run` inside
a `bench.window` span, and copies the `.xplane.pb` to OUT.
"""
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import cell  # noqa: E402


def main(workload, batches, out):
    _, wl, cfg, traffic, _ = cell.load_cell(workload)
    devices, rules = cell.open_chip(cell.ROOT, int(wl["chips"]))
    import jax
    import trace_reduce
    entry = cell.Archive(cfg, traffic, 987654321987, rules, len(devices))
    entry.warm()
    d = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(d, profiler_options=cell._trace_options())
    with cell.annotate("window"):
        for _ in entry.pre.run([(i, entry.bank[i])
                                for i in range(int(batches))]):
            pass
    jax.profiler.stop_trace()
    shutil.copy(trace_reduce.find_xplane(d), out)
    shutil.rmtree(d, ignore_errors=True)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(*sys.argv[1:4])
