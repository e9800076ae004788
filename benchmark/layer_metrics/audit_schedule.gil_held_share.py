"""audit_schedule.gil_held_share: the CPU seconds the pass's threads ran
holding the interpreter lock, over the pipelined schedule's wall.  held =
cpu - released for the lister and each stage; released is only what this
repository's own C can time from inside, so the share is an upper bound
and is reported as read, above 1.0 too."""

STAGES = ("flatten", "dispatch", "collect", "fold_render")
# (cpu key, released key) of mgr.perf for every thread of the pass
THREADS = [("list_cpu", "list_released")] + [
    (f"pipe_{s}_cpu", f"pipe_{s}_released") for s in STAGES]


def read(obs: dict):
    perf = obs["perf"].get("manager", {})
    if not all(k in perf for keys in THREADS for k in keys):
        return None
    wall = perf.get("pipe_wall")
    if not wall:
        return None
    return sum(perf[c] - perf[r] for c, r in THREADS) / wall
