"""python_gc.full_span_s_per_pass: full collections as the program's own
spans have them.  A program that records no such span reads nothing."""

SPAN = "runtime.gc.full"


def read(obs: dict):
    spans = [sp for sp in obs.get("spans") or () if sp["name"] == SPAN]
    if not spans or not obs.get("passes"):
        return None
    return sum(sp["duration_s"] for sp in spans) / obs["passes"]
