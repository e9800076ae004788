"""flatten.glue_s_per_pass: CPU seconds a pass of the calling
thread inside the native columnizer call and outside its GIL-released
phases (cpu - released of evaluator.perf; not clipped)."""

CPU, RELEASED = "fl_columnize_cpu", "fl_columnize_released"


def read(obs: dict):
    perf = obs["perf"].get("evaluator", {})
    if CPU not in perf or RELEASED not in perf or not obs.get("passes"):
        return None
    return (perf[CPU] - perf[RELEASED]) / obs["passes"]
