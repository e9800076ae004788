"""mutlane.scoped_copy_share: of the working copies the batched mutation
lane built in the window, the share it copied along the mutators' location
paths alone (``mutation.core.copy_along``) rather than whole: the
attributes ``copies_scoped`` and ``copies_full`` of the spans
``mutlane.apply``, summed over the window.  A window with no lane span,
with lane spans that carry no such attributes, or with no copy at all
reads nothing."""

SPAN = "mutlane.apply"


def read(obs: dict):
    scoped = full = 0
    for sp in obs.get("spans") or ():
        if sp["name"] != SPAN:
            continue
        attrs = sp["attributes"]
        if "copies_scoped" not in attrs or "copies_full" not in attrs:
            continue
        scoped += attrs["copies_scoped"]
        full += attrs["copies_full"]
    if not scoped + full:
        return None
    return scoped / (scoped + full)
