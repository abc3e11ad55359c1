"""Share of the batches that ran a tail whose cleaned rows were already on
the host when the plan's loop took them, so the loop did not wait on the
copy back (`BatchResult.timings["emit_ready"]`, 1.0 or 0.0, recorded for
the batches that ran a tail), in %."""


def read(rec):
    v = [b["emit_ready"] for b in rec.get("batches") or ()
         if "emit_ready" in b]
    return 100.0 * sum(v) / len(v) if v else None
