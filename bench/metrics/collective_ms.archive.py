"""Device time of the collective operations (the tail's all-gather, the
all-reduces of detection's statistics, any other exchange) a batch, per
chip: each chip's summed collective op durations in the traced window,
the mean over the chips, over the batches the window emitted, in ms."""


def read(rec):
    trace, batches = rec.get("trace"), rec.get("batches")
    if not trace or not trace["devices"] or not batches:
        return None
    coll = [d["collective_s"] for d in trace["devices"]]
    return 1e3 * sum(coll) / len(coll) / len(batches)
