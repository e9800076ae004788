"""What the chip could do at best: the table of peaks, and the least work a
device program has to do, computed from its shapes."""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip.  A device the table does not list is
    an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"benchmark/peaks.json lists no device "
                       f"{device_kind!r} (it lists {sorted(table)})")
    return table[device_kind]


def sweep_work(wire_bytes: float, d2h_bytes: float, objects: int,
               constraints: int) -> tuple:
    """(bytes, operations) the audit sweep's device programs must at least
    move and do for one pass: read every packed column byte that crossed to
    the device once, write every byte that crossed back once, and make one
    8-bit comparison for each (object, constraint) verdict."""
    return wire_bytes + d2h_bytes, float(objects) * constraints


def roofline_share(bytes_moved: float, ops: float, busy_s: float,
                   peaks: dict) -> tuple:
    """(share of the roofline in %, the bound) of a program that was busy
    for ``busy_s``: the least time the chip could take, the larger of bytes
    over peak bytes/s and operations over peak int8 op/s, over the time it
    took."""
    by_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    by_compute = ops / peaks["int8_op_per_s"]
    bound = "memory" if by_memory >= by_compute else "compute"
    return 100.0 * max(by_memory, by_compute) / busy_s, bound
