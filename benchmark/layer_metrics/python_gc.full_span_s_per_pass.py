"""python_gc.full_span_s_per_pass: full collections as the program's own
spans have them.  A window whose tracer recorded spans and none of a full
collection held none: 0.0.  Only a run with no spans at all (no tracer, or
a program that records none) reads nothing."""

SPAN = "runtime.gc.full"


def read(obs: dict):
    spans = obs.get("spans") or ()
    if not spans or not obs.get("passes"):
        return None
    return sum(sp["duration_s"] for sp in spans
               if sp["name"] == SPAN) / obs["passes"]
