"""audit_schedule.critical_occupancy: the busiest slot of the pipelined
schedule over its wall.  A slot is the calling thread's lister or one
stage; a stage with several workers has that many slots' worth of wall."""

import re

STAGE_BUSY = re.compile(r"pipe_(.+)_busy$")


def slots(perf: dict) -> dict:
    """{slot: occupancy} of every slot ``mgr.perf`` accounts for; empty
    where the program keeps no such account."""
    wall = perf.get("pipe_wall")
    if not wall or "list" not in perf:
        return {}
    out = {"list": perf["list"] / wall}
    for key, busy in perf.items():
        m = STAGE_BUSY.match(key)
        if m:
            workers = perf.get(f"pipe_{m.group(1)}_workers") or 1.0
            out[m.group(1)] = busy / (wall * workers)
    return out


def read(obs: dict):
    occupancy = slots(obs["perf"].get("manager", {}))
    return max(occupancy.values()) if occupancy else None
