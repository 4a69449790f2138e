"""Share of the KV rows the window's decode steps read that hold a live
token: the sum of ``kv_rows_live`` over the sum of ``kv_rows_read``, both
attributes of the last ``len(steps)`` ``engine.step`` program spans.  None
where the program records no spans."""
from repro.serve import telemetry


def read(ctx):
    spans = getattr(telemetry, "spans", None)
    n = len(ctx["steps"])
    if spans is None or not n:
        return None
    steps = spans("engine.step")[-n:]
    read_rows = sum(s.attrs["kv_rows_read"] for s in steps)
    if not read_rows:
        return None
    return 100.0 * sum(s.attrs["kv_rows_live"] for s in steps) / read_rows
