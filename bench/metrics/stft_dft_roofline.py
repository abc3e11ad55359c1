"""Share of the roofline bound reached by the `stft_dft` Pallas kernel in the
traced window: the least time the chip needs for the work `bench/work.py`
counts for the calls made (ops over the bf16 peak or bytes over HBM
bandwidth, whichever is larger, from `bench/peaks.json`), over the summed
device time of the kernel's events, all chips together, in %."""


def read(rec):
    trace, work = rec.get("trace"), rec.get("work") or {}
    if not trace or "stft_dft" not in work:
        return None
    seconds = sum(d["kernel_s"].get("stft_dft", 0.0) for d in trace["devices"])
    ops, nbytes = work["stft_dft"]
    return rec["roofline"](ops, nbytes, seconds)
