"""Share of the traced window in which no operation ran on a chip,
averaged over the cell's chips (``bench/trace_reduce.py``). Nothing when
the trace holds no device operation."""


def compute(rec):
    planes = rec.get("trace", {}).get("planes")
    if not planes:
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["traced_window_s"])
