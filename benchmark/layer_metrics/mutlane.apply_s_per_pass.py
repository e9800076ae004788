"""mutlane.apply_s_per_pass: the seconds of the batched mutation lane's
passes over generated objects (spans ``mutlane.apply``) a pass.  A window
with spans and none of the lane (nothing mutated) reads nothing."""

SPAN = "mutlane.apply"


def read(obs: dict):
    spans = [sp for sp in obs.get("spans") or () if sp["name"] == SPAN]
    if not spans or not obs.get("passes"):
        return None
    return sum(sp["duration_s"] for sp in spans) / obs["passes"]
