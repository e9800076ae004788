"""sweep_device_roofline: what the declarative readers cannot say, because
it needs the function that computes the sweep's least work."""

from benchmark import roofline


def read(obs: dict):
    trace, peaks = obs.get("trace"), obs.get("peaks")
    perf = obs["perf"].get("evaluator", {})
    if not trace or not trace["busy_s"] or not peaks \
            or "wire_bytes" not in perf or not obs.get("passes"):
        return None
    n = obs["passes"]
    moved, ops = roofline.sweep_work(perf["wire_bytes"] / n,
                                     perf.get("d2h_bytes", 0.0) / n,
                                     obs["objects"], obs["constraints"])
    share, _bound = roofline.roofline_share(
        moved, ops, trace["busy_s"] / trace["passes"], peaks)
    return share
