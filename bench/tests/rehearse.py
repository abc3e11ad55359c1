"""A run of a benchmark cell on the CPU at a tiny size, for the tests.

`tiny_checkout` copies `bench/` and `BENCHMARK.json` into a directory and
cuts each configuration to 2 long chunks a batch on each device and each
mix to a bank of 8 long chunks. `run_cell` drives `cell.run` there with the chip
check switched off, the kernels on their "ref" path (JAX on the CPU), and
returns the last line it printed.
"""
import contextlib
import io
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def tiny_checkout(dest):
    dest = str(dest)
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    for sub, edit in (("configs", _tiny_config), ("traffic", _tiny_traffic)):
        d = os.path.join(dest, "bench", sub)
        for f in os.listdir(d):
            with open(os.path.join(d, f)) as fh:
                data = json.load(fh)
            edit(data)
            with open(os.path.join(d, f), "w") as fh:
                json.dump(data, fh)
    return dest


def add_split_cell(root, workload, chips):
    """Add to the tiny checkout at `root` a copy of `workload` whose batch
    splits over `chips` devices, as a multi-chip cell's does; returns its
    name."""
    def path(*p):
        return os.path.join(root, *p)
    with open(path("BENCHMARK.json")) as f:
        bench = json.load(f)
    w = dict(next(w for w in bench["workloads"] if w["name"] == workload))
    c = dict(next(c for c in bench["configs"] if c["name"] == w["config"]))
    name, cname = f"{workload}_x{chips}", f"{c['name']}_x{chips}"
    with open(path(c["file"])) as f:
        cfg = json.load(f)
    cfg.update(name=cname, chips=chips)
    _tiny_config(cfg)
    c.update(name=cname, file=f"bench/configs/{cname}.json")
    w.update(name=name, config=cname, chips=chips)
    bench["configs"].append(c)
    bench["workloads"].append(w)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if workload in m.get("workloads", ()):
            m["workloads"].append(name)
    shutil.copy(path("bench", "limits", workload + ".json"),
                path("bench", "limits", name + ".json"))
    for p, data in ((c["file"], cfg), ("BENCHMARK.json", bench)):
        with open(path(p), "w") as f:
            json.dump(data, f)
    return name


def _tiny_config(c):
    c["long_chunks_per_batch"] = 2 * c["chips"]


def _tiny_traffic(t):
    t["bank_long_chunks"] = 8


def run_cell(root, workload, seed=2 ** 31 + 7, seconds=6.0, trace=0):
    """(exit code, parsed last stdout line or None, stdout, stderr) of one
    CPU run of `workload` in the tiny checkout at `root`."""
    import jax
    import cell
    import repro.runtime
    from repro.core import plans
    saved = {k: jax.config.values[k] for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    chip_check = repro.runtime.require_chip
    sample = cell.SAMPLE_LONG
    out, err = io.StringIO(), io.StringIO()
    try:
        repro.runtime.require_chip = lambda: None
        cell.SAMPLE_LONG = 1
        plans.JIT_CACHE.clear()
        jax.clear_caches()
        args = cell.argparse.Namespace(workload=workload, seed=seed,
                                       seconds=seconds, trace=trace)
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = cell.run(args, root)
    finally:
        repro.runtime.require_chip = chip_check
        cell.SAMPLE_LONG = sample
        for k, v in saved.items():
            jax.config.update(k, v)
        if env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env
        plans.JIT_CACHE.clear()
        jax.clear_caches()
    lines = out.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
        else None
    return rc, last, out.getvalue(), err.getvalue()
