"""audit_schedule.idle_unlabelled_share: how much of the device's longest
idle gaps the program's spans cannot put a name to.  ``xplane.label_gaps``
labels a gap with the innermost span open on each thread at its middle,
joined by '+'; the root alone (or '-', no span at all) is no label."""

UNLABELLED = ("audit.sweep", "-")


def read(obs: dict):
    gaps = (obs.get("trace") or {}).get("idle_gaps")
    if not gaps:
        return None
    total = sum(seconds for _label, seconds in gaps)
    if not total:
        return None
    return sum(seconds for label, seconds in gaps
               if label in UNLABELLED) / total
