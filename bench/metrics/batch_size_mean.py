"""`batch_size_mean`: requests fused per dispatch by ``TuningServer``
over the window (``ServerStats.batch_requests / batches``)."""


def read(r: dict):
    c = r.get("counters", {})
    if not c.get("batches"):
        return None
    return c["batch_requests"] / c["batches"]
