"""Mean host time per batch blocked on the keep-mask readback
(`BatchResult.timings["readback_s"]`), in ms."""


def read(rec):
    v = [b["readback_s"] for b in rec.get("batches") or () if "readback_s" in b]
    return 1e3 * sum(v) / len(v) if v else None
