"""Mean host time per batch blocked on the rest of the cleaned rows' copy
back and their host-side re-layout, after the tail program finished
(`BatchResult.timings["d2h_wait_s"]`, recorded for the batches that ran a
tail), in ms."""


def read(rec):
    v = [b["d2h_wait_s"] for b in rec.get("batches") or ()
         if "d2h_wait_s" in b]
    return 1e3 * sum(v) / len(v) if v else None
