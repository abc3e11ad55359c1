"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), the mean over the chips, in
%."""


def read(rec):
    trace = rec.get("trace")
    if not trace or not trace["devices"]:
        return None
    busy = [d["busy_s"] for d in trace["devices"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace["window_s"])
