"""The whole step's share of the chips' peak: model FLOPs per protein
(``bench/flops.py``, recomputation not counted) times proteins per second,
over the chips times the published peak of their device kind
(``bench/peaks.json``). An unknown device kind raises."""
import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def compute(rec):
    with open(PEAKS) as f:
        peaks = json.load(f)["devices"]
    if rec["device_kind"] not in peaks:
        raise KeyError(f"no published peak for {rec['device_kind']!r} in "
                       f"{PEAKS}")
    peak = peaks[rec["device_kind"]]["bf16_flops_per_s"]
    return 100.0 * rec["flops_per_protein"] * rec["proteins_per_s"] / (
        rec["chips"] * peak)
