"""Share of the traced window in which a collective ran on a chip and no
compute did, averaged over the cell's chips (``bench/trace_reduce.py``).
Nothing when the trace holds no collective."""


def compute(rec):
    planes = rec.get("trace", {}).get("planes")
    if not planes or not any(p["collective_s"] for p in planes):
        return None
    exposed = sum(p["exposed_collective_s"] for p in planes) / len(planes)
    return 100.0 * exposed / rec["traced_window_s"]
