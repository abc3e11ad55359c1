#!/usr/bin/env python3
"""One benchmark cell on the chip.

    python3 bench/cell.py --workload NAME --seed N --seconds S --trace 0|1

`BENCHMARK.json` at the checkout's root names the cell's configuration and
traffic mix; everything else is found by name under `bench/`:
`configs/<config>.json` (the deployment), `traffic/<mix>.json` (the mix),
`limits/<workload>.json` (the limits of the numbers that decide `correct`)
and `metrics/<name>.py` (one reader per per-layer metric, found by listing
the directory). Adding a cell adds files; it edits none.

A run refuses to start without a TPU, with fewer chips than the cell asks
for, or with kernels that do not resolve to compiled Pallas. It then builds
the cell's inputs from `--seed`, warms every shape the traffic can reach,
measures for `--seconds` (the profiler off), or with `--trace 1` measures
the same window under the profiler, checks what the window produced
against the plain reference (`bench/reference.py`), and prints one JSON
line last. The numbers compared, each beside its limit, are the last lines
on standard error and the last key of that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
KERNELS = ("fir_hpf", "stft_dft", "fused_tail")
SPAN = "bench."
# long chunks compared with the reference, drawn from the seed among the
# batches the window produced (whole batches, at least one)
SAMPLE_LONG = 48
COMPILE_EVENT_PREFIX = "/jax/core/compile/"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age():
    """Seconds since this process started (Linux /proc clock ticks)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


def log(*a):
    print(*a, flush=True)


def read_json(path):
    with open(path) as f:
        return json.load(f)


class GcMeter:
    """Python's garbage collections from construction to `close`: how
    many, how many of the oldest generation, and the longest pause."""

    def __init__(self):
        self.count, self.gen2, self.longest = 0, 0, 0.0
        self._t = None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.gen2 += info["generation"] == 2
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self)


class CompileMeter:
    """Compile seconds (trace + lower + backend compile or cache fetch) and
    backend compiles, from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0

    def __call__(self, event, duration, **_):
        if event.startswith(COMPILE_EVENT_PREFIX):
            self.seconds += duration
        if event == BACKEND_COMPILE_EVENT:
            self.programs += 1


# -- the cell's files -------------------------------------------------------

def load_cell(name, root=ROOT):
    """(BENCHMARK.json, its workload entry, config, traffic, limits)."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    c = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = os.path.join(root, "bench")
    return (bench, w, read_json(os.path.join(root, c["file"])),
            read_json(os.path.join(here, "traffic", w["traffic"] + ".json")),
            read_json(os.path.join(here, "limits", name + ".json")))


def metric_files(root=ROOT):
    """{metric name: path} of every reader in bench/metrics."""
    d = os.path.join(root, "bench", "metrics")
    return {f[:-3]: os.path.join(d, f) for f in sorted(os.listdir(d))
            if f.endswith(".py") and not f.startswith("_")}


def load_reader(name, path):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench, workload):
    """(end-to-end metric entries, per-layer metric entries) this cell
    reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return e2e, layer


def program_config(geometry):
    from repro.configs.serf_audio import AudioPipelineConfig
    return AudioPipelineConfig(**{k: tuple(v) if isinstance(v, list) else v
                                  for k, v in geometry.items()})


def annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(SPAN + name)


def per_long(g):
    return int(round(g["long_split_s"] / g["final_split_s"]))


def pow2_buckets(rows, multiple):
    """Every padded survivor count the pow2 tail buckets can take for a
    `rows`-row batch split `multiple` ways."""
    cap = -(-rows // multiple) * multiple
    out, size = [], multiple
    while size < cap:
        out.append(size)
        size *= 2
    return out + [cap]


def warm_targets(buckets):
    """A survivor count in the middle of each bucket's range."""
    return [(lo + hi + 1) // 2 for lo, hi in zip([0] + buckets, buckets)]


def bucket_of(n, buckets):
    """The tail bucket that `n` survivors pad to (None for none)."""
    return next((b for b in buckets if n <= b), None) if n else None


class Reservoir:
    """A uniform sample of `k` items from a stream of unknown length,
    drawn from the seed."""

    def __init__(self, k, rng):
        self.k, self.rng, self.n, self.items = k, rng, 0, []

    def offer(self, make):
        if self.n < self.k:
            self.items.append(make())
        else:
            j = int(self.rng.integers(0, self.n + 1))
            if j < self.k:
                self.items[j] = make()
        self.n += 1


# -- the archive entry: Preprocessor(plan="async").run ------------------------

class Archive:
    def __init__(self, cfg, traffic, seed, rules, chips):
        import gen
        from repro.core.plans import Preprocessor
        if cfg["entry"]["kind"] != "archive":
            raise ValueError(f"the harness drives the archive entry only, not "
                             f"{cfg['entry']['kind']!r}")
        self.g = cfg["geometry"]
        self.B = cfg["long_chunks_per_batch"]
        n = traffic["bank_long_chunks"] // self.B
        if n < 1:
            raise ValueError("the traffic's bank holds no whole batch")
        with annotate("bank_build"):
            x, labels = gen.long_chunks(seed, traffic, self.g, n * self.B,
                                        group=self.B)
        self.bank = [x[i * self.B:(i + 1) * self.B] for i in range(n)]
        self.labels = labels
        self.traffic = traffic
        self.rng = gen.rng_for(seed, 3)
        entry = cfg["entry"]
        self.pre = Preprocessor(program_config(self.g), rules,
                                plan=entry["plan"], depth=entry["depth"],
                                pad_multiple=chips)
        self.chips = chips
        self.audio_per_batch = self.B * self.g["long_split_s"]

    def warm(self):
        """Every shape the window can reach, through the timed entry alone:
        one graded batch per pow2 tail bucket of the batch's 5 s rows (as
        many bird-song chunks as the middle of the bucket, the rest
        silence), then one pass over the bank."""
        import gen
        rows = self.B * per_long(self.g)
        buckets = pow2_buckets(rows, self.chips)
        want = warm_targets(buckets)
        reached = set()
        batches = gen.graded_batches(self.traffic, self.g, self.B, want)
        for res in self.pre.run(enumerate(batches)):
            reached.add(bucket_of(res.n_kept, buckets))
        missed = [b for b in buckets if b not in reached]
        if missed:   # a program that keeps other chunks than the reference
            log(f"set-up: the warm-up reached no tail bucket {missed} (kept "
                f"counts aimed at {want})")
        for _ in self.pre.run(list(enumerate(self.bank))):
            pass

    def window(self, seconds, k):
        """Drive the entry for `seconds`; returns the record with a sample
        of the batches emitted, `k` long chunks' worth.

        The plan keeps `depth` batches dispatched ahead, so the window
        closes by sending nothing more once `seconds` are up, waiting for
        every batch already sent, and reading the clock after that wait:
        all the batches count, over all that time."""
        bank, n = self.bank, len(self.bank)
        sample = Reservoir(max(1, k // self.B), self.rng)
        t0 = time.perf_counter()
        t_end = t0 + seconds

        def stream():
            i = 0
            while time.perf_counter() < t_end:
                with annotate("bank_draw"):
                    item = (i, bank[i % n])
                yield item
                i += 1

        batches = []
        it = self.pre.run(stream())
        with annotate("window"):
            while True:
                with annotate("run_step"):
                    res = next(it, None)
                if res is None:
                    break
                batches.append(dict(res.timings, n_kept=res.n_kept,
                                    at_s=time.perf_counter() - t0))
                if res.n_kept:
                    sample.offer(lambda: self._keep(res))
        elapsed = time.perf_counter() - t0
        return {"seconds": elapsed, "batches": batches,
                "attempted": len(batches), "failed": 0,
                "audio_s": len(batches) * self.audio_per_batch,
                "sample": sample.items,
                "kept": sum(b["n_kept"] for b in batches),
                "chunks": len(batches) * self.B * per_long(self.g)}

    @staticmethod
    def _keep(res):
        import numpy as np
        return {"wid": res.wid, "keep": np.asarray(res.det.keep),
                "cleaned": res.cleaned}

    def inputs(self, item):
        return self.bank[item["wid"] % len(self.bank)]


# -- correctness --------------------------------------------------------------

def compare(sample, inputs, ref):
    """The numbers that decide `correct`, for the sampled answers against
    the reference `ref` run on the same inputs: 5 s chunks whose keep flag
    differs, and the root-mean-square error of the cleaned survivors both
    keep relative to the reference's root-mean-square (the largest absolute
    error rides along for the log)."""
    import numpy as np
    out = {"keep_mismatch": 0, "chunks_compared": 0}
    err2 = ref2 = in_err2 = in_ref2 = 0.0
    worst, row_rel = None, []
    edge = ref.win
    for item in sample:
        r = ref.run(inputs(item))
        pk, rk = np.asarray(item["keep"]), r["keep"]
        out["keep_mismatch"] += int((pk != rk).sum())
        out["chunks_compared"] += int(pk.size)
        both = pk & rk
        a = np.asarray(item["cleaned"], np.float64)[both[pk]]
        b = np.asarray(r["cleaned"], np.float64)[both[rk]]
        if a.size:
            e2, b2 = (a - b) ** 2, b ** 2
            err2 += float(e2.sum())
            ref2 += float(b2.sum())
            in_err2 += float(e2[:, edge:-edge].sum())
            in_ref2 += float(b2[:, edge:-edge].sum())
            row_rel += list(np.sqrt(e2.sum(1) / np.maximum(b2.sum(1), 1e-300)))
            worst = max(worst or 0.0, float(np.abs(a - b).max()))
    if ref2 > 0:
        out["cleaned_rel_rms"] = math.sqrt(err2 / ref2)
        out["cleaned_max_err"] = worst
        out["cleaned_row_rel_median"] = float(np.median(row_rel))
        out["cleaned_inner_rel_rms"] = math.sqrt(in_err2 / max(in_ref2, 1e-300))
    return out


def control(sample, inputs, geometry):
    """The control: the sampled answers as the reference gives them in
    three bfloat16 passes, put in the program's place. `compare` must find
    it not correct."""
    import reference
    ctl = reference.Reference(geometry, "bf16_3x")
    out = []
    for item in sample:
        r = ctl.run(inputs(item))
        out.append({"wid": item["wid"], "keep": r["keep"],
                    "cleaned": r["cleaned"]})
    return out


def verdict(numbers, limits):
    """(correct, {name: {"value", "limit"}}) for the numbers with a limit;
    a number with a limit that the run could not read fails."""
    check, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        check[name] = {"value": v, "limit": limit}
        if v is None or not v <= limit:
            ok = False
    return ok, check


# -- the run --------------------------------------------------------------

def device_info(devices):
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def open_chip(root, chips):
    """Point JAX's compile cache at the checkout, refuse to run without a
    TPU, with kernels that are not compiled Pallas, or with another number
    of chips than the cell asks for (a multi-chip cell takes every chip of
    the host); returns (devices, sharding rules)."""
    cache_dir = os.path.join(root, ".jax_cache")
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    for p in (os.path.join(root, "bench"), os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.runtime import enable_compile_cache, require_chip
    enable_compile_cache()
    require_chip()
    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devices)}")
    if chips > 1 and len(devices) != chips:
        raise SystemExit(f"a {chips}-chip cell splits its batch over every "
                         f"chip of the host; JAX sees {len(devices)}")
    from repro.distributed.sharding import NULL_RULES, ShardingRules
    from repro.launch.mesh import make_local_mesh
    rules = ShardingRules(make_local_mesh()) if chips > 1 else NULL_RULES
    return devices[:chips], rules


def run(args, root=ROOT):
    """One run of the cell in the checkout at `root`; returns the exit
    code."""
    bench, wl, cfg, traffic, limits = load_cell(args.workload, root)
    e2e, layer = cell_metrics(bench, args.workload)
    readers = metric_files(root)
    missing = [m["name"] for m in layer if m["name"] not in readers]
    if missing:
        raise SystemExit(f"no reader in bench/metrics for {missing}")
    here = os.path.join(root, "bench")
    import jax
    devices, rules = open_chip(root, int(wl["chips"]))
    from repro.kernels import backend
    chips = len(devices)
    d0 = devices[0]
    log(f"device: platform {d0.platform}, device_kind {d0.device_kind}, "
        f"count {len(devices)}; kernels (use_pallas, interpret) = "
        f"{backend.resolve()}; compile cache "
        f"{os.environ['JAX_COMPILATION_CACHE_DIR']}")
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)

    import gen
    t = time.perf_counter()
    entry = Archive(cfg, traffic, args.seed, rules, chips)
    log(f"set-up: inputs from seed {args.seed} in "
        f"{time.perf_counter() - t:.3f} s; labels "
        f"{dict(zip(gen.LABELS, [int((entry.labels == i).sum()) for i in range(4)]))}")
    t = time.perf_counter()
    entry.warm()
    log(f"set-up: warm-up {time.perf_counter() - t:.3f} s, compile "
        f"{meter.seconds:.3f} s over {meter.programs} backend compiles")
    compile_s, programs = meter.seconds, meter.programs

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    setup_s = process_age()
    gcm = GcMeter()
    rec = entry.window(args.seconds, SAMPLE_LONG)
    gcm.close()
    if args.trace:
        jax.profiler.stop_trace()
    compiles_in = meter.programs - programs
    dev = device_info(devices)
    log(f"window: {rec['attempted']} attempted, compiles inside the "
        f"window {compiles_in} ({meter.seconds - compile_s:.3f} s), "
        f"peak device memory {dev['memory_peak_bytes']} bytes, keep rate "
        f"{rec['kept']}/{rec['chunks']}; {gcm.count} garbage collections "
        f"({gcm.gen2} of the oldest generation), longest "
        f"{1e3 * gcm.longest:.1f} ms")
    log(pace_summary(rec["batches"], args.seconds))

    metrics, breakdown = {}, None
    if not args.trace:
        for m in e2e:
            metrics[m["name"]] = {"value": e2e_value(m["name"], rec, setup_s),
                                  "unit": m["unit"]}
    else:
        red = reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = sum(d["busy_s"] for d in red["devices"]) \
            / len(red["devices"])
        dev["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        lrec = layer_record(rec, red, cfg, compile_s,
                            load_peaks(d0.device_kind, os.path.join(
                                here, "peaks.json")))
        for m in layer:
            v = load_reader(m["name"], readers[m["name"]])(lrec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the program's state before the reference runs on the chip
    sample, inputs = rec["sample"], entry.inputs
    del rec["batches"]
    gc.collect()
    t = time.perf_counter()
    import reference
    numbers = compare(sample, inputs, reference.Reference(cfg["geometry"]))
    log(f"reference: {len(sample)} sampled answers, "
        f"{numbers['chunks_compared']} 5 s chunks, "
        f"{time.perf_counter() - t:.3f} s; {json.dumps(numbers)}")
    correct, check = verdict(numbers, limits)
    if compiles_in:
        correct = False
        log(f"not correct: {compiles_in} programs compiled inside the window")
    for name in [k for k, v in metrics.items()
                 if not math.isfinite(v["value"])]:
        correct = False
        log(f"not correct: {name} has no finite value ({metrics.pop(name)})")
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics,
           "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    for name, c in check.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def pace_summary(batches, seconds):
    """One line on how evenly the window emitted: batches in each whole
    second, the gaps between emissions, and the host's time blocked on the
    keep mask and on the cleaned rows."""
    import numpy as np
    at = np.array([b["at_s"] for b in batches])
    if at.size < 2:
        return f"pace: {at.size} batches emitted"
    per_s = np.bincount(at[at < int(seconds)].astype(int),
                        minlength=int(seconds))
    gaps = np.diff(at)
    wait = sum(b.get("readback_s", 0.0) + b.get("emit_s", 0.0)
               for b in batches)
    return (f"pace: batches a second min {per_s.min()} median "
            f"{np.median(per_s):g} max {per_s.max()}; gaps ms median "
            f"{1e3 * np.median(gaps):.1f} p99 "
            f"{1e3 * np.quantile(gaps, 0.99):.1f} max {1e3 * gaps.max():.1f}, "
            f"{int((gaps > 0.2).sum())} over 200 ms; host blocked "
            f"{wait:.3f} s, dispatching "
            f"{sum(b.get('dispatch_s', 0.0) for b in batches):.3f} s")


def e2e_value(name, rec, setup_s):
    if name == "setup_s":
        return setup_s
    if name == "audio_rate":
        return rec["audio_s"] / rec["seconds"]
    raise KeyError(f"the harness measures no end-to-end metric {name!r}")


def reduce_trace(trace_dir):
    import trace_reduce as trace
    devices, host = trace.load(trace.find_xplane(trace_dir), SPAN)
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    window = trace.span_named(host, SPAN + "window")
    return trace.reduce(devices, host, window, KERNELS,
                        skip_spans=(SPAN + "window",))


def layer_record(rec, red, cfg, compile_s, peaks):
    """What the per-layer readers read."""
    import work
    g = cfg["geometry"]
    out = {"compile_s": compile_s, "trace": red,
           "roofline": lambda ops, b, s: work.roofline_share(ops, b, s, peaks)}
    win, hop = g["stft_window"], g["stft_hop"]
    s15 = int(g["detect_split_s"] * g["target_rate_hz"])
    s5 = int(g["final_split_s"] * g["target_rate_hz"])
    n15 = int(round(g["long_split_s"] / g["detect_split_s"]))
    bs = rec["batches"]
    out["batches"] = bs
    rows15 = len(bs) * cfg["long_chunks_per_batch"] * n15
    out["work"] = {"stft_dft": work.stft_dft(rows15, s15, win, hop)}
    real = sum(b["n_kept"] for b in bs)
    if real:
        out["work"]["fused_tail"] = work.fused_tail(real, s5, win, hop)
    return out


def load_peaks(device_kind, path):
    """The published peaks of `device_kind`; a device not in the table is
    an error, not a default."""
    table = read_json(path)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} in {path}")
    return table[device_kind]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
