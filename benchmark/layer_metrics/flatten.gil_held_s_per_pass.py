"""flatten.gil_held_s_per_pass: CPU seconds a pass of the
flatten stage's threads outside the columnizer's GIL-released phases
(cpu - released of mgr.perf; not clipped)."""

CPU, RELEASED = "pipe_flatten_cpu", "pipe_flatten_released"


def read(obs: dict):
    perf = obs["perf"].get("manager", {})
    if CPU not in perf or RELEASED not in perf or not obs.get("passes"):
        return None
    return (perf[CPU] - perf[RELEASED]) / obs["passes"]
