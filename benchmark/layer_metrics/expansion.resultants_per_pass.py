"""expansion.resultants_per_pass: the implied objects the audit's generator
stage audited a pass, from the attribute ``resultants`` of the spans
``expansion.stage`` with ``phase == "audit"`` (the inner span of the same
name, which counts the same resultants again, is left out)."""

SPAN = "expansion.stage"


def read(obs: dict):
    counts = [sp["attributes"].get("resultants")
              for sp in obs.get("spans") or ()
              if sp["name"] == SPAN
              and sp["attributes"].get("phase") == "audit"]
    if not counts or None in counts or not obs.get("passes"):
        return None
    return sum(counts) / obs["passes"]
