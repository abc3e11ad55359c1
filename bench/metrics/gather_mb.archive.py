"""Mean MB (10**6 bytes) that each chip received in the fused tail's
all-gather of the batch, over the batches that ran a tail
(`BatchResult.timings["gather_bytes"]`)."""


def read(rec):
    v = [b["gather_bytes"] for b in rec.get("batches") or ()
         if "gather_bytes" in b and b.get("n_kept")]
    return 1e-6 * sum(v) / len(v) if v else None
