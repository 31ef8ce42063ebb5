"""Mean host milliseconds per step spent in next(loader) and the trainer's
batch placement (harness spans around the two calls)."""


def read(res):
    f = res["facts"]
    if not f.get("steps") or "input_wait" not in f.get("spans_s", {}):
        return None
    return 1e3 * f["spans_s"]["input_wait"] / f["steps"]
