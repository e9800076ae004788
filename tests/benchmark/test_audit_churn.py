"""``audit-churn`` (PR 39): a cluster that changes between every two audit
passes; a mix that is no cell yet and runs as ``full.audit-sweep``, its
control, under ``--traffic audit-churn``.  The epochs are a pure function
of the configuration and the seed, change only what the mix says (kind
counts stand, no name and no bytes recur, a replaced Pod keeps everything
but its name and, for some, its image, a touch rewrites ``resourceVersion``
alone), the ledger owes every pass the totals of its own cluster, the
interpreter's sample reaches the last epoch brought in, the manifest
resolves with the mix, and the mix runs end to end at toy size: as it
stands (correct), with a lister that serves an epoch late and with an audit
plane that keeps a violation of a deleted Pod (not correct, each by the
number that guards it).  A mix without the block takes the path it always
took.  Nothing here times the system under test."""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import audit, churn, cluster, manifest  # noqa: E402

MIX = "audit-churn"
CONTROL = "full.audit-sweep"
RUN = f"{CONTROL}.under.{MIX}"  # the run's name, and its work directory's
SEED = 2147483999
EPOCHS = 6
OLD_COMPARED = [
    "sample_audit_short", "sample_totals_differ", "sample_kept_differ",
    "sample_totals_missing", "device_pairs_differ", "reference_sample_empty",
    "setup_pass_short", "passes_short", "passes_differ", "window_empty"]
NEW_COMPARED = [
    "epochs_ran_out", "touch_moved_a_verdict", "kept_short", "kept_stale",
    "kept_unfounded", "churn_pairs_differ", "churn_sample_short",
    "kept_messages_differ"]


def toy() -> tuple:
    """(configuration, churn block) at ``--rehearse`` sizes."""
    cell = manifest.Cell(CONTROL, rehearse=True, traffic=MIX)
    return cell.config, cell.traffic["churn"]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """One shard of the toy corpus and the first epochs over it."""
    cfg, block = toy()
    work = str(tmp_path_factory.mktemp("churn"))
    path = os.path.join(work, "corpus.0.jsonl")
    cluster.write_shard(cfg["cluster"], cfg["objects"], SEED, 0, path,
                        cfg["referential_kinds"], {})
    churn.write_epochs(cfg["cluster"], cfg["objects"], SEED, block, [path],
                       work, 0, EPOCHS)
    with open(path, "rb") as f:
        base = [ln.rstrip(b"\n") for ln in f]
    return cfg, block, work, [path], base


# --- the epochs ---------------------------------------------------------------

def repository(image: str) -> str:
    return (image.partition("@")[0] if "@" in image
            else image.rpartition(":")[0])


def test_the_epochs_are_a_pure_function_of_configuration_and_seed(
        world, tmp_path):
    cfg, block, work, paths, _base = world
    again = str(tmp_path)
    # any range of epochs can be made alone, in any order
    churn.write_epochs(cfg["cluster"], cfg["objects"], SEED, block, paths,
                       again, 3, EPOCHS)
    churn.write_epochs(cfg["cluster"], cfg["objects"], SEED, block, paths,
                       again, 0, 3)
    for e in range(EPOCHS):
        assert churn.read_epoch(again, e) == churn.read_epoch(work, e)
    churn.write_epochs(cfg["cluster"], cfg["objects"], SEED + 1, block,
                       paths, again, 0, 1)
    assert churn.read_epoch(again, 0) != churn.read_epoch(work, 0)


def test_an_epoch_changes_what_the_mix_says_and_nothing_else(world):
    cfg, block, work, _paths, base = world
    n = cfg["objects"]
    changed = round(block["share_per_pass"] * n)
    replaced = round(changed * block["replaced"])
    kinds = churn.kinds_by_position(cfg["cluster"], n)
    assert kinds == [json.loads(raw)["kind"] for raw in base]
    now = list(base)
    pool = set(cluster.Cluster(cfg["cluster"], n, SEED)._images)
    newest: dict = {}  # position -> the epoch that changed it last
    seen_images: set = set()  # the new ones of earlier epochs
    for e in range(EPOCHS):
        rows = churn.read_epoch(work, e)
        assert len(rows) == changed == len({pos for pos, *_ in rows})
        assert [op for _, op, _, _ in rows] == (
            [churn.REPLACED] * replaced
            + [churn.TOUCHED] * (changed - replaced))
        new_images = 0
        for pos, op, prev, raw in rows:
            old, new = json.loads(now[pos]), json.loads(raw)
            # kind counts, group sizes and chunk counts never change
            assert new["kind"] == kinds[pos]
            assert prev == newest.get(pos, -1)
            newest[pos] = e
            if op == churn.REPLACED:
                # a rollout: the Pod as it stood, under a new name and, for
                # a new build, its first container on a new image
                assert new["kind"] == "Pod"
                assert new["metadata"].pop("name") != \
                    old["metadata"].pop("name")
                old["metadata"].pop("resourceVersion", None)
                first = new["spec"]["containers"][0]
                if first["image"] not in pool | seen_images:
                    new_images += 1
                    seen_images.add(first["image"])
                    was = old["spec"]["containers"][0]
                    assert repository(first.pop("image")) == repository(
                        was.pop("image"))
                assert new == old
            else:
                # a write in place: resourceVersion and nothing else
                assert new["metadata"].pop("resourceVersion")
                old["metadata"].pop("resourceVersion", None)
                assert new == old
            now[pos] = raw
        assert new_images == round(replaced * block["new_image_share"])
        # referential kinds and Namespaces are touched, never replaced
        assert all(op == churn.TOUCHED for pos, op, _, _ in rows
                   if kinds[pos] != "Pod")
    touched_kinds = {kinds[pos] for e in range(EPOCHS)
                     for pos, op, _, _ in churn.read_epoch(work, e)
                     if op == churn.TOUCHED}
    assert {"Pod", "Ingress", "Service"} <= touched_kinds


def test_no_name_and_no_bytes_recur(world):
    cfg, _block, work, _paths, base = world
    names = collections.Counter(churn.identity(raw) for raw in base)
    seen = collections.Counter(base)
    pool = set(cluster.Cluster(cfg["cluster"], cfg["objects"], SEED)._images)
    images = set(pool)
    now = list(base)
    for e in range(EPOCHS):
        for pos, op, _prev, raw in churn.read_epoch(work, e):
            seen[raw] += 1
            if op == churn.REPLACED:
                names[churn.identity(raw)] += 1
                image = json.loads(raw)["spec"]["containers"][0]["image"]
                # a rollout keeps the image or brings one no object ran
                if image != json.loads(
                        now[pos])["spec"]["containers"][0]["image"]:
                    assert image not in images
                    images.add(image)
            now[pos] = raw
    assert max(names.values()) == 1 and max(seen.values()) == 1
    assert len(images) > len(pool)


def test_as_many_epochs_as_the_stated_cap_and_never_fewer_than_set_up_uses():
    mix = manifest.read_json(manifest.traffic_path(MIX))
    block = mix["churn"]
    # the block holds what defines the traffic and nothing else
    assert sorted(block) == ["new_image_share", "replaced", "settle_passes",
                             "share_per_pass"]
    assert not any(key.startswith("churn.") for key in mix["rehearse"])
    assert churn.epochs_wanted(block, 51.0, 1.6) == 64
    assert churn.epochs_wanted(block, 51.0, 1.0) == 102
    assert churn.epochs_wanted(block, 0.0, 1.0) == \
        churn.epochs_least(block) == 11
    assert churn.SAMPLE_FIRST + churn.SAMPLE_LATER >= 1024
    assert churn.SAMPLE_FIRST >= churn.SAMPLE_FIRST_EPOCHS


def test_the_sample_reaches_the_last_epoch_brought_in(world):
    cfg, _block, work, _paths, _base = world
    n = cfg["objects"]
    first = churn.sample_of(work, n, SEED, range(2), 16)
    assert first == churn.sample_of(work, n, SEED, range(2), 16)
    assert first != churn.sample_of(work, n, SEED + 1, range(2), 16)
    later = churn.sample_of(work, n, SEED, range(2, EPOCHS), 30)
    assert len(first) == 16 and len(later) == 30
    # spread evenly, every epoch represented, each object in the version
    # that epoch made of it
    by_epoch = collections.Counter(vid // n - 1 for vid, _raw in later)
    assert sorted(by_epoch) == list(range(2, EPOCHS))
    assert max(by_epoch.values()) - min(by_epoch.values()) <= 1
    for vid, raw in first + later:
        rows = {pos: r for pos, _op, _prev, r in
                churn.read_epoch(work, vid // n - 1)}
        assert rows[vid % n] == raw
    # more epochs than objects to draw: the last one is still there
    few = churn.sample_of(work, n, SEED, range(EPOCHS), 2)
    assert len(few) == 2 and few[-1][0] // n - 1 == EPOCHS - 1


def test_a_replaced_pod_follows_the_version_that_stood_there(world):
    """A position changed twice: the second change starts from what the
    first made, a rollout of a rollout keeps the first's image."""
    cfg, block, _work, _paths, base = world
    n = cfg["objects"]
    plan = churn.Plan(cfg["cluster"], n, SEED, block)
    twice = None
    for e, changes in enumerate(plan.epochs()):
        twice = next((c for c in changes if c.before is not None
                      and c.op == churn.REPLACED), twice)
        if twice or e > 200:
            break
    assert twice is not None
    before = json.loads(churn.materialize(twice.before, base[twice.pos]))
    after = json.loads(churn.materialize(twice, base[twice.pos]))
    assert after["metadata"]["name"] == f"pod-{twice.serial}"
    assert "resourceVersion" not in after["metadata"]
    if not twice.new_image:
        assert after["spec"] == before["spec"]
    assert after["metadata"].get("labels") == before["metadata"].get("labels")


@pytest.mark.parametrize("work,a_child,cores,children", [
    (28831, 4096, 13, 8), (451, 4096, 13, 1), (0, 64, 13, 1),
    (1500, 64, 13, 12), (1500, 64, 8, 7), (300, 64, 1, 1)])
def test_children_are_as_many_as_the_work_keeps_busy(monkeypatch, work,
                                                     a_child, cores,
                                                     children):
    monkeypatch.setattr(os, "cpu_count", lambda: cores)
    assert churn.children_for(work, a_child) == children


class FakeSetUp:
    """A run, a manager and the epochs of one, for ``audit.settle``: pass
    ``i`` asks XLA for ``asks[i]`` executables."""

    def __init__(self, asks: list, block: dict):
        self.asks, self.block, self.rows = asks, block, []

    def bring_in(self) -> bool:
        self.rows.append([])
        return True

    def audit(self) -> str:
        return f"pass over epoch {len(self.rows) - 1}"

    def compiles_between(self, lo: float, hi: float) -> int:
        assert lo <= hi
        return self.asks[len(self.rows) - 1]


@pytest.mark.parametrize("asks,passes", [
    ([0], 1), ([3, 0], 2), ([3, 1, 0], 3), ([1, 1, 1, 1], 3)])
def test_set_up_settles_until_a_churned_pass_asks_xla_for_nothing(asks,
                                                                  passes):
    fake = FakeSetUp(asks, toy()[1])
    settled = audit.settle(fake, fake, fake)
    assert [(e, r, asked) for e, r, asked, _s in settled] == [
        (i, f"pass over epoch {i}", asks[i]) for i in range(passes)]
    assert all(s >= 0.0 for _e, _r, _asked, s in settled)


def test_the_lister_lists_the_overlay_with_one_lookup_an_object(world):
    _cfg, _block, work, paths, base = world
    overlay: dict = {}
    lister = churn.lister_of(paths, overlay)
    assert [o.raw for o in lister()] == [o.raw for o in
                                         audit.lister_of(paths)()] == base
    rows = churn.read_epoch(work, 0)
    overlay.update((pos, raw) for pos, _, _, raw in rows)
    listed = [o.raw for o in lister()]
    assert len(listed) == len(base)
    assert {i for i, (a, b) in enumerate(zip(listed, base)) if a != b} == {
        pos for pos, *_ in rows}


# --- what a pass owes ----------------------------------------------------------

V = collections.namedtuple("V", "kind namespace name message")
Pass = collections.namedtuple("Pass", "total_violations kept")
K1, K2 = ("K8sPSPPrivilegedContainer", "psp"), ("K8sRequiredLabels", "owner")


def pod(name: str, rv: str | None = None) -> bytes:
    meta = {"name": name, "namespace": "ns-0"}
    if rv:
        meta["resourceVersion"] = rv
    return cluster.dumps({"apiVersion": "v1", "kind": "Pod",
                          "metadata": meta})


def small_ledger() -> churn.Ledger:
    """Ten objects; position 3 violates K1 and is replaced in epoch 0 by a
    Pod that violates K2, which epoch 1 touches; position 5 violates K1
    and is touched in epoch 0."""
    n = 10
    base = {3: pod("pod-3"), 5: pod("pod-5")}
    rows = [[(3, churn.REPLACED, -1, pod("pod-10")),
             (5, churn.TOUCHED, -1, pod("pod-5", "1"))],
            [(3, churn.TOUCHED, 0, pod("pod-10", "2"))]]
    pairs = {(K1, 3), (K2, churn.version_id(n, 0, 3)),
             (K2, churn.version_id(n, 1, 3)),
             (K1, 5), (K1, churn.version_id(n, 0, 5))}
    return churn.Ledger(n, rows, base, pairs, {K1: 4, K2: 0})


def kept(*names: str) -> list:
    return [V("Pod", "ns-0", name, "m") for name in names]


def test_a_pass_owes_the_totals_of_its_own_cluster():
    ledger = small_ledger()
    assert ledger.totals == [{K1: 3, K2: 1}, {K1: 3, K2: 1}]
    assert ledger.touch_moved == 0
    sound = Pass({K1: 3, K2: 1}, {K1: kept("pod-5", "a", "b"),
                                  K2: kept("pod-10")})
    for e in (0, 1):
        assert not any(ledger.pass_problems(e, sound, 20).values())
    # the totals of the cluster as it was: a stale answer
    stale = Pass({K1: 4, K2: 0}, {K1: kept("pod-5", "a", "b", "c"), K2: []})
    assert ledger.pass_problems(0, stale, 20)["totals"] == 2


def test_a_kept_violation_names_an_object_of_that_pass():
    ledger = small_ledger()
    totals = {K1: 3, K2: 1}
    deleted = Pass(totals, {K1: kept("pod-3", "a", "b"), K2: kept("pod-10")})
    assert ledger.pass_problems(0, deleted, 20) == {
        "totals": 0, "kept_short": 0, "kept_stale": 1, "kept_unfounded": 0}
    # a changed object kept for what its new version does not violate
    unfounded = Pass(totals, {K1: kept("pod-10", "a", "b"),
                              K2: kept("pod-10")})
    assert ledger.pass_problems(1, unfounded, 20)["kept_unfounded"] == 1
    short = Pass(totals, {K1: kept("pod-5", "a"), K2: kept("pod-10")})
    assert ledger.pass_problems(0, short, 20)["kept_short"] == 1
    assert ledger.pass_problems(0, short, 2)["kept_short"] == 0
    over = Pass(totals, {K1: kept("pod-5", "a", "b"), K2: kept("pod-10")})
    assert ledger.pass_problems(0, over, 2)["kept_short"] == 1
    assert ledger.version(0, 3) == 13 and ledger.version(1, 3) == 23
    assert ledger.version(1, 5) == 15 and ledger.version(1, 7) == 7


def test_a_touch_that_moves_a_verdict_is_counted():
    ledger = churn.Ledger(10, [[(5, churn.TOUCHED, -1, pod("pod-5", "1"))]],
                          {5: pod("pod-5")}, {(K1, 5)}, {K1: 1})
    assert ledger.touch_moved == 1 and ledger.totals == [{K1: 0}]


def test_kept_messages_are_the_interpreters_of_the_bytes_listed():
    raw = pod("pod-5", "1")
    versions = {("Pod", "ns-0", "pod-5"): raw}
    got = Pass({K1: 1}, {K1: [V("Pod", "ns-0", "pod-5", "c1 is privileged"),
                              V("Pod", "ns-0", "pod-5", "c2 is privileged")]})
    both = {raw: {K1: ["c2 is privileged", "c1 is privileged"]}}
    assert churn.kept_message_problems(got, versions, both) == 0
    assert churn.kept_message_problems(
        got, versions, {raw: {K1: ["c1 is privileged"]}}) == 1
    assert churn.kept_message_problems(got, versions, {}) == 1
    assert churn.kept_message_problems(got, {}, both) == 1


def test_an_unchanged_object_is_found_by_its_name(world):
    _cfg, _block, _work, paths, base = world
    names = [churn.identity(base[i]) for i in (0, 17, len(base) - 1)]
    ghost = ("Pod", "ns-0", "pod-999999999")
    found = churn.locate(paths, names + [ghost])
    assert {name: pos for name, (pos, _raw) in found.items()} == {
        names[0]: 0, names[1]: 17, names[2]: len(base) - 1}
    assert all(raw == base[pos] for pos, raw in found.values())


# --- the manifest ----------------------------------------------------------------

def test_the_manifest_resolves_with_the_mix_and_its_two_metrics(monkeypatch):
    assert manifest.check() == []
    m = manifest.read_json(manifest.MANIFEST)
    mix = manifest.read_json(manifest.traffic_path(MIX))
    # no cell yet, and the file says why and how it runs meanwhile
    assert not any(w["traffic"] == MIX for w in m["workloads"])
    assert "not a cell of BENCHMARK.json yet" in mix["status"]
    assert f"--workload {CONTROL} --traffic {MIX}" in mix["status"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    cell, control = manifest.Cell(CONTROL, traffic=MIX), manifest.Cell(CONTROL)
    # the control lends the mix its configuration, to the byte, and its
    # metrics; the run has a name, and so a work directory, of its own
    assert cell.name == RUN and control.name == CONTROL
    assert cell.config == control.config and cell.chips == control.chips
    assert cell.traffic == mix and control.traffic["name"] == "audit-sweep"
    assert cell.end_to_end == control.end_to_end
    assert cell.per_layer == control.per_layer
    mine = [p["name"] for p in cell.per_layer]
    for name in ("list.changed_share", "list.new_strings_per_pass"):
        assert name in mine
        entry = next(p for p in m["per_layer"] if p["name"] == name)
        assert entry["layer"] == "list" and entry["moves"] == "audit_pass_s"
        assert manifest.read_json(manifest.metric_path(name))["read"] == {
            "from": "counts", "key": name.partition(".")[2]}
    # a mix that changes the cluster says what it assumed, and of each
    # value whether a document of the repository bears it out
    assert {"churn.share_per_pass", "churn.replaced", "churn.replaced_pod",
            "churn.new_image_share", "churn.kinds"} <= set(mix["assumed"])
    assert "IN THE REPOSITORY" in mix["assumed"]["churn.share_per_pass"]
    for key in ("churn.replaced", "churn.new_image_share"):
        assert "No source" in mix["assumed"][key]
    assert "listed in that pass" in mix["guarantees"]["kept_violations"]
    real = manifest.read_json

    def without_assumed(path):
        doc = real(path)
        if path == manifest.traffic_path(MIX):
            del doc["assumed"]
        return doc

    monkeypatch.setattr(manifest, "read_json", without_assumed)
    assert any("'assumed'" in fault for fault in manifest.check())


def test_a_churn_mix_needs_totals_of_violating_objects(monkeypatch, capsys):
    from benchmark import run as run_py

    real = manifest.read_json

    def exact(path):
        doc = real(path)
        if path.endswith("library-full.json"):
            doc["audit"]["exact_totals"] = True
        return doc

    monkeypatch.setattr(manifest, "read_json", exact)
    with pytest.raises(ValueError, match="exact_totals"):
        run_py.main(["--workload", CONTROL, "--traffic", MIX, "--rehearse",
                     "--seconds", "1"])


# --- the cell, end to end at toy size ----------------------------------------------

def rehearse(capsys, mix: str | None = MIX, *args: str) -> tuple:
    """(the result line, the notes) of one rehearsal of the control's
    configuration, under ``mix`` or under its own."""
    from benchmark import run as run_py

    under = ["--traffic", mix] if mix else []
    assert run_py.main(["--workload", CONTROL, *under, "--rehearse", "--seed",
                        str(SEED), "--seconds", "3", *args]) == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert "rehearsal" in line and list(line)[-1] == "compared"
    last = captured.err.strip().splitlines()[-1]
    assert json.loads(last.partition("compared: ")[2]) == line["compared"]
    assert line["correct"] is all(c["value"] <= c["limit"]
                                  for c in line["compared"].values())
    with open(os.path.join(ROOT, "benchmark", ".cache",
                           RUN if mix else CONTROL, "notes.json")) as f:
        return line, json.load(f)


def test_rehearse_the_mix(capsys):
    """The whole path at toy sizes on whatever JAX finds: every pass of the
    window lists a cluster 1% away from the pass before, is held to that
    cluster's totals, and the traced run reads the two new metrics.  Half a
    minute."""
    line, notes = rehearse(capsys, MIX, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    assert list(line["compared"]) == OLD_COMPARED + NEW_COMPARED
    assert all(c == {"value": 0, "limit": 0}
               for c in line["compared"].values())
    assert notes["problems"] == []
    state = notes["churn"]
    n, passes = notes["objects"], notes["passes"]
    settled = len(state["settle"])
    assert 1 <= settled <= 3
    assert state["settle"][-1]["executables_asked_for"] == 0
    assert state["epochs_brought_in"] == settled + passes
    assert state["epochs_made"] >= state["epochs_brought_in"]
    assert set(state["changed"]) == {round(0.01 * n)}
    assert state["executables_by_pass"] == [0] * passes
    metrics = line["metrics"]
    assert notes["workload"] == RUN
    listed = {p["name"] for p in manifest.Cell(CONTROL).per_layer}
    assert listed - set(metrics) <= {"sweep_device_roofline"}
    assert metrics["list.changed_share"]["value"] == pytest.approx(
        round(0.01 * n) / n)
    grown = state["vocabulary"][1] - state["vocabulary"][0]
    assert metrics["list.new_strings_per_pass"]["value"] == pytest.approx(
        grown / passes)
    # every replaced Pod brings a name, a fifth of them an image
    assert 29 <= grown / passes <= 29 + 6
    assert metrics["entry.compiles_in_window"]["value"] == 0.0


def test_a_mix_without_the_block_takes_the_path_it_always_took(capsys):
    line, notes = rehearse(capsys, None, "--trace", "1")
    assert line["correct"] is True
    assert list(line["compared"]) == OLD_COMPARED
    assert "churn" not in notes
    assert not any(phase.startswith("churn")
                   for phase in notes["setup_phases_s"])
    assert line["metrics"]["list.changed_share"]["value"] == 0.0
    assert line["metrics"]["list.new_strings_per_pass"]["value"] == 0.0


def an_epoch_listed_a_pass_late(monkeypatch) -> None:
    """The lister's sixth listing (a pass of the window) serves every
    object the newest epoch changed under the bytes of the pass before."""
    real = churn.lister_of

    def lister_of(paths, overlay):
        calls, before = [0], [{}]

        def lister():
            calls[0] += 1
            view = before[0] if calls[0] == 6 else overlay
            yield from real(paths, view)()
            before[0] = dict(overlay)

        return lister

    monkeypatch.setattr(churn, "lister_of", lister_of)


def a_deleted_pod_kept(monkeypatch) -> None:
    """From its fifth pass on the audit plane keeps, for one constraint, a
    violation of the first Pod that epoch 0 replaced."""
    from gatekeeper_tpu.audit.manager import AuditManager

    real = AuditManager.audit
    calls = [0]
    work = os.path.join(ROOT, "benchmark", ".cache", RUN)

    def audit_(self, *a, **kw):
        out = real(self, *a, **kw)
        calls[0] += 1
        if calls[0] >= 5:
            pos = churn.read_epoch(work, 0)[0][0]
            raw = churn.read_positions(
                [os.path.join(work, "corpus.0.jsonl")], {pos})[pos]
            _kind, namespace, name = churn.identity(raw)
            key = next(k for k, vs in out.kept.items()
                       if vs and vs[0].kind == "Pod")
            out.kept[key][0] = dataclasses.replace(
                out.kept[key][0], namespace=namespace, name=name)
        return out

    monkeypatch.setattr(AuditManager, "audit", audit_)


def a_total_altered(monkeypatch) -> None:
    """The audit plane's sixth pass reports one constraint's total one too
    high: an answer altered where it is produced."""
    from gatekeeper_tpu.audit.manager import AuditManager

    real = AuditManager.audit
    calls = [0]

    def audit_(self, *a, **kw):
        out = real(self, *a, **kw)
        calls[0] += 1
        if calls[0] == 6:
            key = next(iter(out.total_violations))
            out.total_violations[key] += 1
        return out

    monkeypatch.setattr(AuditManager, "audit", audit_)


@pytest.mark.parametrize("fault,fails", [
    (an_epoch_listed_a_pass_late, "kept_stale"),
    (a_deleted_pod_kept, "kept_stale"),
    (a_total_altered, "passes_differ")])
def test_a_planted_stale_answer_is_caught(capsys, monkeypatch, fault, fails):
    """The harness's look for a chip skipped (``--rehearse``), the rest of a
    run driven with a wrong answer planted underneath: ``correct`` is
    false, and the number that says so is the one that guards it.  A
    listing served a pass late shows in the names kept (a Pod that a
    rollout replaced) and, where the epoch moved a verdict, in the totals:
    a rollout seldom does, and at toy size this seed's does not."""
    fault(monkeypatch)
    line, notes = rehearse(capsys)
    assert line["correct"] is False
    assert line["compared"][fails]["value"] > 0
    assert notes["problems"]
    # set-up's own comparisons are the sound program's
    assert all(line["compared"][name]["value"] == 0 for name in OLD_COMPARED
               if name != "passes_differ")
    if fault is a_total_altered:
        assert line["compared"]["passes_differ"]["value"] == 1
        assert sum(c["value"] for c in line["compared"].values()) == 1
