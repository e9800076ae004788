"""expansion.busy_s_per_pass: the wall seconds of the audit's generator
stage a pass, from the spans ``expansion.stage`` that carry
``phase == "audit"``.  ``ExpansionStage.expand_batch`` opens a span of the
same name inside each, without the attribute: it is part of the outer one
and is not counted twice.  A window with spans and no such span (the stage
off, or no generator listed) reads nothing."""

SPAN = "expansion.stage"


def stage_spans(obs: dict) -> list:
    """The generator stage's spans of the window: one an audit pass."""
    return [sp for sp in obs.get("spans") or ()
            if sp["name"] == SPAN
            and sp["attributes"].get("phase") == "audit"]


def read(obs: dict):
    spans = stage_spans(obs)
    if not spans or not obs.get("passes"):
        return None
    return sum(sp["duration_s"] for sp in spans) / obs["passes"]
