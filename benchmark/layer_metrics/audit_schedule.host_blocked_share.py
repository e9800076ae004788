"""audit_schedule.host_blocked_share: of the busy seconds of the slots
that are Python on the host from end to end, the share their threads did
not run.  Flatten and collect are left out: their busy time is mostly
inside calls that release the GIL on purpose (the C columnizer's own
threads, the wait for the device), so busy - cpu says nothing there."""

# slot -> (busy key, cpu key) of mgr.perf
HOST_SLOTS = {
    "list": ("list", "list_cpu"),
    "dispatch": ("pipe_dispatch_busy", "pipe_dispatch_cpu"),
    "fold_render": ("pipe_fold_render_busy", "pipe_fold_render_cpu"),
}


def read(obs: dict):
    perf = obs["perf"].get("manager", {})
    if not all(k in perf for keys in HOST_SLOTS.values() for k in keys):
        return None
    busy = sum(perf[b] for b, _ in HOST_SLOTS.values())
    if not busy:
        return None
    blocked = sum(max(0.0, perf[b] - perf[c])
                  for b, c in HOST_SLOTS.values())
    return blocked / busy
