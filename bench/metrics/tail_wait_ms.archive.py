"""Mean host time per batch blocked until the tail program's output exists
on the device (`BatchResult.timings["tail_wait_s"]`, recorded for the
batches that ran a tail), in ms."""


def read(rec):
    v = [b["tail_wait_s"] for b in rec.get("batches") or ()
         if "tail_wait_s" in b]
    return 1e3 * sum(v) / len(v) if v else None
