"""Mean host time per batch to copy it to the device and enqueue its detect
program (`BatchResult.timings["dispatch_s"]`), in ms."""


def read(rec):
    v = [b["dispatch_s"] for b in rec.get("batches") or () if "dispatch_s" in b]
    return 1e3 * sum(v) / len(v) if v else None
