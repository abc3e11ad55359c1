"""Reduce a profiler trace (an `.xplane.pb` that `jax.profiler` wrote) to
the numbers the per-layer metrics read.

A TPU's plane is named `/device:TPU:<n>`; its `XLA Ops` line holds one
event per operation the chip ran, named by the operation's HLO text
(`%fused_tail.1 = f32[...] custom-call(...)`): a Pallas kernel's
instruction takes the kernel's `name`. The host's plane holds the
benchmark's own `jax.profiler.TraceAnnotation` spans on the same clock.

- busy: the union of the op intervals of a device inside the window;
- idle share: 1 - busy / window;
- kernel time: the summed durations of the ops whose instruction is the
  kernel (`<kernel>.<n>`);
- collective time: the summed durations of all-gather, all-reduce,
  reduce-scatter, all-to-all and collective-permute ops (their async
  start/done halves included);
- idle gaps: the stretches inside the window where a device ran nothing,
  each named by the benchmark span that covers most of it.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute")
_INSTANCE = re.compile(r"\.\d+$")


def find_xplane(log_dir):
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def instruction(event_name):
    """`fused_tail.1` from `%fused_tail.1 = f32[...] custom-call(...)`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path, host_prefix=""):
    """(device ops {device: [(start_ns, end_ns, instruction)]}, host spans
    [(start_ns, end_ns, name)] whose name starts with `host_prefix`) from
    one `.xplane.pb`."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     instruction(ev.name)) for ev in line.events)
            elif plane.name.startswith("/host:"):
                host += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events
                         if ev.name.startswith(host_prefix)]
    return devices, host


def union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def kernel_of(instr, kernels):
    """The kernel an instruction is a call of, or None."""
    base = _INSTANCE.sub("", instr)
    return base if base in kernels else None


def span_named(host, name):
    """(start_ns, end_ns) of the first host span called `name`."""
    for s, e, n in host:
        if n == name:
            return s, e
    raise KeyError(f"no host span {name!r} in the trace")


def reduce(devices, host, window, kernels, top=10, skip_spans=()):
    """The trace's numbers. Kernel, collective and op times sum every op
    the trace holds (the trace spans the work it is compared with); busy
    time and idle gaps are those inside `window` = (start_ns, end_ns).

    Returns {"window_s", "devices": [{"device", "busy_s", "kernel_s",
    "kernel_calls", "collective_s"}], "device_ops": [[op, s]],
    "idle_gaps": [[span, s]]}; device_ops sums over the devices by kernel,
    else by HLO instruction (an instruction name can recur in several
    programs); idle_gaps are the longest gaps of any device, each named by
    the host span that covers most of it."""
    lo, hi = window
    per_dev, op_time, gaps = [], collections.Counter(), []
    spans = [(s, e, n) for s, e, n in host if n not in skip_spans]
    for dev in sorted(devices):
        ops = devices[dev]
        ks, calls, coll = collections.Counter(), collections.Counter(), 0
        for s, e, instr in ops:
            d = (e - s) / 1e9
            k = kernel_of(instr, kernels)
            if k:
                ks[k] += d
                calls[k] += 1
            if COLLECTIVE.match(instr):
                coll += d
            op_time[k or instr] += d
        busy = union([(s, e) for s, e, _ in ops], lo, hi)
        per_dev.append({"device": dev,
                        "busy_s": sum(e - s for s, e in busy) / 1e9,
                        "kernel_s": dict(ks), "kernel_calls": dict(calls),
                        "collective_s": coll})
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                 if e > s]
    gaps.sort(reverse=True)
    return {"window_s": (hi - lo) / 1e9, "devices": per_dev,
            "device_ops": [[k, v] for k, v in op_time.most_common(top)],
            "idle_gaps": [[cover(spans, s, e), d / 1e9]
                          for d, s, e in gaps[:top]]}


def cover(spans, lo, hi):
    """Name of the host span overlapping [lo, hi] the most (the shortest
    on a tie, so the innermost span wins); "none" where nothing does."""
    best, key = "none", (0, 0)
    for s, e, n in spans:
        ov = min(e, hi) - max(s, lo)
        if ov > 0 and (ov, -(e - s)) > key:
            best, key = n, (ov, -(e - s))
    return best
