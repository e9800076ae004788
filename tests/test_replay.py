"""``gator replay``: the offline policy time machine.

1. Corpus ingest: capture-mode flight-recorder JSONL → replayable
   records, skip-and-count for malformed lines, a crashed recorder's
   torn tail, non-validate endpoints, shed/error decisions, no-body
   entries.
2. THE replay differential: an identical candidate replays the corpus
   with ZERO divergences, bit-identical decisions/messages/codes, and
   ZERO fresh lowerings (the shared on-disk compile cache answers every
   template).
3. The rollout preview: a candidate missing one deny-firing constraint
   attributes every ``newly_allowed`` divergence to exactly that
   constraint, with top offenders by namespace/kind.
4. ``gator replay`` CLI: exit codes (2 usage, 1 on non-bit-identical
   differential), JSON and table output.
5. Spill-at-rv replay: a ``--snapshot-spill`` directory replays its
   resident objects at the audit enforcement point against the spilled
   verdict store — differential bit-identity, constraint-drop diff,
   section integrity, and the TWO-WAY vocab prefix rule (snapshot ⊆
   current is a hit; a diverged overlap is a counted vocab miss).
6. The whole round trip once more on a second, smaller corpus served
   from the warm cache: record, read, replay identical and modified.
7. ``gator decisions`` + flight-recorder sink: truncated-tail vs
   malformed accounting, torn-tail sink repair on append.

Wall budget: one module-scoped corpus (5-template library slice, 90
recorded admissions) and one shared on-disk compile cache; every
candidate load after the first is all cache hits.
"""

from __future__ import annotations

import copy
import json
import os
import shutil

import pytest

from gatekeeper_tpu.apis.constraints import AUDIT_EP
from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
from gatekeeper_tpu.gator import reader, replay_cmd
from gatekeeper_tpu.metrics import registry as M
from gatekeeper_tpu.metrics.registry import MetricsRegistry
from gatekeeper_tpu.ops.flatten import RowIdMap  # noqa: F401 (import check)
from gatekeeper_tpu.parallel.sharded import ShardedEvaluator, make_mesh
from gatekeeper_tpu.replay import core
from gatekeeper_tpu.snapshot import (ClusterSnapshot, SnapshotConfig,
                                     SnapshotSpill, templates_digest)
from gatekeeper_tpu.sync.source import FakeCluster
from gatekeeper_tpu.utils.synthetic import make_cluster_objects
from gatekeeper_tpu.utils.unstructured import name_of
from tests import traffic_helpers


def _record(n_requests, cache_dir, sink_dir):
    """Serve ``n_requests`` synthetic admissions over the 5-template
    library slice and read the sink back as a replay corpus."""
    sink = os.path.join(str(sink_dir), "decisions.jsonl")
    docs = traffic_helpers.library_docs()
    bodies = traffic_helpers.admission_bodies(n_requests)
    serve = traffic_helpers.serve_and_record(docs, bodies, sink, cache_dir)
    records, counts = core.read_corpus(sink)
    return {"cache_dir": cache_dir, "sink": sink, "docs": docs,
            "serve": serve, "records": records, "counts": counts}


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """A recorded corpus: a serving stack (real
    ValidationHandler + capture-mode flight recorder) answers 90
    synthetic admissions over a 5-template library slice; the sink and
    the warm compile cache are shared module-wide."""
    return _record(90, str(tmp_path_factory.mktemp("replay-cc")),
                   tmp_path_factory.mktemp("replay-sink"))


def _replay(corpus, docs, **kw):
    """One candidate replay lane over the module corpus (fresh runtime,
    warm disk cache), generation coordinator stopped on the way out."""
    runtime = core.load_candidate(
        docs, compile_cache_dir=corpus["cache_dir"],
        metrics=kw.pop("load_metrics", None))
    try:
        return core.replay_decisions(corpus["records"], runtime, **kw)
    finally:
        gc = getattr(runtime.driver, "gen_coord", None)
        if gc is not None:
            gc.stop()


def _dropped_deny_constraint(corpus):
    """The first (sorted) constraint the recorded corpus blames for a
    deny — the modified-candidate lanes drop it."""
    denied = set()
    for r in corpus["records"]:
        if r.get("decision") == "deny":
            denied.update(core.recorded_constraints(r.get("message", "")))
    assert denied, "corpus recorded no denies — fixture seed regressed"
    return sorted(denied)[0]


# --- 1. corpus ingest ------------------------------------------------------

def test_corpus_capture_complete(corpus):
    counts = corpus["counts"]
    assert counts["replayed"] == len(corpus["records"]) == 90
    assert counts["lines"] == 90  # every served admission recorded
    assert corpus["serve"]["denies"] > 0
    for r in corpus["records"]:
        assert isinstance(r["request"], dict)
        assert r["decision"] in ("allow", "deny")


def test_read_corpus_skip_and_count(tmp_path):
    good = {"endpoint": "validate", "decision": "allow", "uid": "g",
            "request": {"uid": "g"}}
    deny = {"endpoint": "validate", "decision": "deny", "uid": "d",
            "message": "[some-con] no", "request": {"uid": "d"}}
    path = tmp_path / "sink.jsonl"
    path.write_text(
        json.dumps(good) + "\n"
        + "{half a line\n"                                 # malformed
        + "42\n"                                           # not a record
        + json.dumps({"endpoint": "audit", "decision": "allow",
                      "request": {}}) + "\n"               # endpoint
        + json.dumps({"endpoint": "validate", "decision": "shed",
                      "request": {}}) + "\n"               # unreplayable
        + json.dumps({"endpoint": "validate",
                      "decision": "deny"}) + "\n"          # no body
        + json.dumps(deny) + "\n"
        + '{"endpoint": "validate", "deci')                # torn tail
    records, counts = core.read_corpus(str(path))
    assert [r["uid"] for r in records] == ["g", "d"]
    assert counts == {"lines": 8, "replayed": 2, "malformed": 2,
                      "endpoint": 1, "unreplayable_decision": 1,
                      "no_body": 1, "truncated_tail": 1}


def test_read_corpus_limit(corpus):
    records, counts = core.read_corpus(corpus["sink"], limit=10)
    assert len(records) == 10 and counts["replayed"] == 10


# --- 2. the identical-candidate differential -------------------------------

def test_identical_candidate_bit_identical_zero_lowerings(corpus):
    metrics = MetricsRegistry()
    report = _replay(corpus, corpus["docs"], differential=True,
                     metrics=metrics, skipped=corpus["counts"],
                     load_metrics=metrics)
    assert report["records"] == 90
    assert report["divergences_total"] == 0
    assert report["newly_denied"] == report["newly_allowed"] == 0
    assert report["message_changed"] == report["errors"] == 0
    assert report["by_constraint"] == {}
    diff = report["differential"]
    assert diff["bit_identical"] and diff["checked"] == 90
    assert diff["mismatches_total"] == 0
    # the recorded and candidate decision mixes agree exactly
    assert report["recorded"] == report["candidate"]
    # zero fresh lowerings: the serving pass populated the disk cache,
    # the candidate load answered every template from it
    cc = report["compile_cache"]
    assert cc["misses"] == 0 and cc["hits"] > 0
    assert report["lowering"]["templates"] == 5
    # metrics: replayed outcome counted, no divergence series touched
    assert metrics.get_counter(M.REPLAY_RECORDS,
                               {"outcome": "replayed"}) == 90
    assert metrics.counter_total(M.REPLAY_DIVERGENCE) == 0
    assert metrics.get_gauge(M.REPLAY_SECONDS) is not None


# --- 3. the rollout preview (modified candidate) ---------------------------

def test_modified_candidate_attributes_newly_allowed(corpus):
    drop = _dropped_deny_constraint(corpus)
    docs = [d for d in corpus["docs"]
            if not (reader.is_constraint(d) and name_of(d) == drop)]
    metrics = MetricsRegistry()
    report = _replay(corpus, docs, metrics=metrics)
    assert report["newly_allowed"] > 0
    assert report["newly_denied"] == 0
    per = report["by_constraint"][drop]
    assert per["newly_allowed"] > 0 and per["newly_denied"] == 0
    for d in report["divergences"]:
        assert d["kind"] == "newly_allowed"
        assert drop in d["constraints_removed"]
    # the offender axes name where the divergences landed
    assert sum(c for _n, c in report["top_offenders"]["namespace"]) == \
        report["divergences_total"]
    assert sum(c for _n, c in report["top_offenders"]["kind"]) == \
        report["divergences_total"]
    assert "differential" not in report  # candidate mode only
    assert metrics.get_counter(M.REPLAY_DIVERGENCE,
                               {"kind": "newly_allowed"}) == \
        report["newly_allowed"]


# --- 4. the CLI ------------------------------------------------------------

def _docs_file(tmp_path, docs, name="candidate.json"):
    p = tmp_path / name
    p.write_text(json.dumps(docs, default=str))
    return str(p)


def test_replay_cli_differential_json(corpus, tmp_path, capsys):
    cand = _docs_file(tmp_path, corpus["docs"])
    rc = replay_cmd.run_cli([
        "-f", corpus["sink"], "--candidate", cand, "--differential",
        "--compile-cache", corpus["cache_dir"], "-o", "json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["differential"]["bit_identical"]
    assert report["records"] == 90
    assert report["compile_cache"]["misses"] == 0


def test_replay_cli_mismatch_exits_1(corpus, tmp_path, capsys):
    drop = _dropped_deny_constraint(corpus)
    cand = _docs_file(tmp_path, [
        d for d in corpus["docs"]
        if not (reader.is_constraint(d) and name_of(d) == drop)])
    rc = replay_cmd.run_cli([
        "-f", corpus["sink"], "--candidate", cand, "--differential",
        "--compile-cache", corpus["cache_dir"]])
    assert rc == 1
    out = capsys.readouterr().out
    assert "MISMATCHES" in out
    assert drop in out  # per-constraint attribution in the table


def test_replay_cli_usage_errors(corpus, tmp_path, capsys):
    cand = _docs_file(tmp_path, corpus["docs"])
    # exactly one corpus source required
    assert replay_cmd.run_cli(["--candidate", cand]) == 2
    assert replay_cmd.run_cli([
        "-f", corpus["sink"], "--from-spill", "x",
        "--candidate", cand]) == 2
    # candidate required
    assert replay_cmd.run_cli(["-f", corpus["sink"]]) == 2
    # unreadable candidate / empty doc set are reported, not tracebacks
    assert replay_cmd.run_cli([
        "-f", corpus["sink"], "--candidate",
        str(tmp_path / "nope.yaml")]) == 1
    empty = _docs_file(tmp_path, [], name="empty.json")
    assert replay_cmd.run_cli([
        "-f", corpus["sink"], "--candidate", empty]) == 1
    capsys.readouterr()


# --- 5. spill-at-rv replay -------------------------------------------------

@pytest.fixture(scope="module")
def spilled(corpus, tmp_path_factory):
    """A --snapshot-spill directory: the candidate docs' library audits
    60 synthetic objects through the snapshot path, then spills."""
    root = str(tmp_path_factory.mktemp("replay-spill"))
    runtime = core.load_candidate(corpus["docs"],
                                  compile_cache_dir=corpus["cache_dir"])
    evaluator = ShardedEvaluator(runtime.driver, make_mesh(),
                                 violations_limit=20)
    cluster = FakeCluster()
    for o in make_cluster_objects(60, seed=23):
        cluster.apply(copy.deepcopy(o))
    snap = ClusterSnapshot(evaluator, SnapshotConfig())
    mgr = AuditManager(
        runtime.client, lister=lambda: iter(cluster.list()),
        config=AuditConfig(audit_source="snapshot", chunk_size=64,
                           exact_totals=False, pipeline="off"),
        evaluator=evaluator, snapshot=snap)
    run = mgr.audit()
    spill = SnapshotSpill(root)
    wrote = spill.save(snap, templates=templates_digest(runtime.client))
    assert wrote["ok"] and wrote["rows"] == 60
    return {"root": root, "run": run,
            "tdig": templates_digest(runtime.client)}


def test_spill_replay_differential_bit_identical(corpus, spilled):
    spill = core.read_spill(spilled["root"])
    assert spill["rows"] == 60 and len(spill["objects"]) == 60
    assert spill["verdicts"], "spill recorded no violating rows"
    runtime = core.load_candidate(corpus["docs"],
                                  compile_cache_dir=corpus["cache_dir"])
    report = core.replay_spill(spill, runtime, differential=True)
    assert report["divergences_total"] == 0
    assert report["by_constraint"] == {}
    assert report["differential"]["bit_identical"]
    assert report["compile_cache"]["misses"] == 0


def test_spill_replay_modified_candidate_newly_clean(corpus, spilled):
    spill = core.read_spill(spilled["root"])
    drop = sorted(n for n, rows in spill["verdicts"].items() if rows)[0]
    docs = [d for d in corpus["docs"]
            if not (reader.is_constraint(d) and name_of(d) == drop)]
    runtime = core.load_candidate(docs,
                                  compile_cache_dir=corpus["cache_dir"])
    report = core.replay_spill(spill, runtime)
    per = report["by_constraint"][drop]
    assert per["newly_clean"] == len(spill["verdicts"][drop])
    assert per["newly_violating"] == 0
    assert all(d["constraint"] == drop and d["kind"] == "newly_clean"
               for d in report["divergences"])


def test_read_spill_rejects_corrupt_section(spilled, tmp_path):
    d = str(tmp_path / "spill-copy")
    shutil.copytree(spilled["root"], d)
    rows_p = os.path.join(d, "snapshot.rows.pkl")
    with open(rows_p, "r+b") as f:
        f.seek(os.path.getsize(rows_p) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(ValueError, match="sha256"):
        core.read_spill(d)


def test_spill_vocab_two_way_prefix_rule(corpus, spilled):
    """The fleet-mode vocab gate on ``SnapshotSpill.load``: current ⊆
    snapshot replays the tail; snapshot ⊆ current (a sibling cluster
    grew the shared vocab past the spill) is ALSO a hit with nothing to
    replay; a diverged overlap is a counted (non-deleting) miss."""
    from gatekeeper_tpu.snapshot.persist import MISS_VOCAB

    runtime = core.load_candidate(corpus["docs"],
                                  compile_cache_dir=corpus["cache_dir"])
    ev = ShardedEvaluator(runtime.driver, make_mesh(),
                          violations_limit=20)
    cons = [c for c in runtime.client.constraints()
            if c.actions_for(AUDIT_EP)]
    vocab = runtime.driver.vocab

    # restart shape: boot vocab is a prefix of the spilled table
    snap_a = ClusterSnapshot(ev, SnapshotConfig())
    assert SnapshotSpill(spilled["root"]).load(
        snap_a, cons, templates=spilled["tdig"]) is not None
    spilled_len = len(vocab._to_str)  # tail replayed: cur == snapshot

    # sibling-churn shape: the shared vocab grew PAST the spill
    for i in range(5):
        vocab.intern(f"sibling-churn-{i}")
    snap_b = ClusterSnapshot(ev, SnapshotConfig())
    sp = SnapshotSpill(spilled["root"])
    assert sp.load(snap_b, cons, templates=spilled["tdig"]) is not None
    assert sp.miss_reasons == {}
    assert len(vocab._to_str) == spilled_len + 5  # nothing re-interned

    # adversarial churn: a conflicting sid inside the overlap — the
    # spill itself is fine (files stay), but it must never load here
    vocab._to_str[spilled_len - 1] = "conflicting-intern"
    snap_c = ClusterSnapshot(ev, SnapshotConfig())
    sp2 = SnapshotSpill(spilled["root"])
    assert sp2.load(snap_c, cons, templates=spilled["tdig"]) is None
    assert sp2.miss_reasons == {MISS_VOCAB: 1}
    assert snap_c.stale  # untouched on a miss
    assert os.path.exists(os.path.join(spilled["root"], "snapshot.json"))


def test_replay_cli_from_spill(corpus, spilled, tmp_path, capsys):
    cand = _docs_file(tmp_path, corpus["docs"])
    rc = replay_cmd.run_cli([
        "--from-spill", spilled["root"], "--candidate", cand,
        "--differential", "--compile-cache", corpus["cache_dir"],
        "-o", "json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["source"] == "spill" and report["rows"] == 60
    assert report["differential"]["bit_identical"]


# --- 6. the round trip on a second corpus, served from the warm cache ------

def test_bench_replay_smoke(corpus, tmp_path):
    second = _record(60, corpus["cache_dir"], tmp_path)
    assert len(second["records"]) == 60
    ident = _replay(second, second["docs"], differential=True)
    assert ident["differential"]["bit_identical"]
    cc = ident["compile_cache"]
    assert cc["misses"] == 0 and cc["hits"] > 0  # zero fresh lowerings
    assert ident["divergences_total"] == 0
    # modified lane: drop the first constraint with recorded denies (a
    # corpus that recorded none would assert on noise)
    if second["serve"]["denies"]:
        drop = _dropped_deny_constraint(second)
        docs = [d for d in second["docs"]
                if not (reader.is_constraint(d) and name_of(d) == drop)]
        assert _replay(second, docs)["newly_allowed"] > 0


# --- 7. gator decisions + sink hardening -----------------------------------

def test_decisions_cmd_truncated_vs_malformed(tmp_path, capsys):
    from gatekeeper_tpu.gator import decisions_cmd

    path = tmp_path / "sink.jsonl"
    path.write_text(
        json.dumps({"ts": 1.0, "endpoint": "validate",
                    "decision": "allow", "uid": "u1"}) + "\n"
        + "{corrupt mid-file\n"
        + "17\n"
        + '{"ts": 2.0, "endpoint": "validate", "decis')  # torn tail
    doc = decisions_cmd.read_decisions(str(path))
    assert [e["uid"] for e in doc["decisions"]] == ["u1"]
    assert doc["malformed"] == 2
    assert doc["truncated"] == 1
    rc = decisions_cmd.run_cli(["-f", str(path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "2 malformed" in out and "1 truncated" in out


def test_flightrec_sink_torn_tail_repaired_on_append(tmp_path):
    """A crashed recorder leaves a torn final line; the next recorder
    appending to the same sink must not fuse its first record onto it."""
    from gatekeeper_tpu.observability import flightrec

    path = tmp_path / "sink.jsonl"
    path.write_text('{"endpoint": "validate", "decision": "al')  # torn
    rec = flightrec.FlightRecorder(capacity=8, sink_path=str(path),
                                   capture=True)
    rec.record("validate", "allow", uid="after-crash",
               request={"uid": "after-crash"})
    rec.close()
    records, counts = core.read_corpus(str(path))
    assert counts["malformed"] == 1  # the torn line, confined
    assert counts.get("truncated_tail", 0) == 0
    assert [r["uid"] for r in records] == ["after-crash"]
    assert records[0]["request"] == {"uid": "after-crash"}


# --- 4. namespace-selector replay fidelity ---------------------------------

NS_SEL_TEMPLATE = {
    "apiVersion": "templates.gatekeeper.sh/v1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "k8snssel"},
    "spec": {"crd": {"spec": {"names": {"kind": "K8sNsSel"}}},
             "targets": [{
                 "target": "admission.k8s.gatekeeper.sh",
                 "rego": """
package k8snssel

violation[{"msg": msg}] {
  input.review.object.kind == "Pod"
  msg := "pod in selected namespace"
}
"""}]},
}
NS_SEL_CONSTRAINT = {
    "apiVersion": "constraints.gatekeeper.sh/v1beta1",
    "kind": "K8sNsSel",
    "metadata": {"name": "deny-team-a-pods"},
    "spec": {"match": {
        "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}],
        "namespaceSelector": {"matchLabels": {"team": "a"}}}},
}
NS_AUDIT_TEMPLATE = {
    "apiVersion": "templates.gatekeeper.sh/v1",
    "kind": "ConstraintTemplate",
    "metadata": {"name": "k8snsspill"},
    "spec": {"crd": {"spec": {"names": {"kind": "K8sNsSpill"}}},
             "targets": [{
                 "target": "admission.k8s.gatekeeper.sh",
                 "rego": """
package k8snsspill

violation[{"msg": msg}] {
  input.review.object.kind == "Pod"
  msg := "audited"
}
"""}]},
}
NS_AUDIT_CONSTRAINT = {
    "apiVersion": "constraints.gatekeeper.sh/v1beta1",
    "kind": "K8sNsSpill",
    "metadata": {"name": "ns-spill-audit"},
    "spec": {"match": {
        "kinds": [{"apiGroups": [""], "kinds": ["Pod"]}]}},
}


def _ns_doc(name, team):
    return {"apiVersion": "v1", "kind": "Namespace",
            "metadata": {"name": name, "labels": {"team": team}}}


def _ns_pod(i, ns):
    return {"apiVersion": "v1", "kind": "Pod",
            "metadata": {"name": f"p{i}", "namespace": ns}, "spec": {}}


@pytest.fixture(scope="module")
def ns_corpus(tmp_path_factory):
    """Recorded decisions whose verdicts depended on the RECORDED
    cluster's Namespace labels (alpha: team=a denied), plus a snapshot
    spill of that cluster — the namespace source of record."""
    from gatekeeper_tpu.observability import flightrec
    from gatekeeper_tpu.webhook.policy import ValidationHandler

    sink = os.path.join(str(tmp_path_factory.mktemp("ns-sink")),
                        "decisions.jsonl")
    runtime = core.load_candidate([NS_SEL_TEMPLATE, NS_SEL_CONSTRAINT])
    ns_live = {"alpha": _ns_doc("alpha", "a"),
               "beta": _ns_doc("beta", "b")}
    handler = ValidationHandler(runtime.client,
                                namespace_lookup=ns_live.get)
    bodies = []
    for i, ns in enumerate(["alpha", "beta"] * 6):
        bodies.append({"apiVersion": "admission.k8s.io/v1",
                       "kind": "AdmissionReview",
                       "request": {"uid": f"ns-{i:04d}",
                                   "kind": {"group": "", "version": "v1",
                                            "kind": "Pod"},
                                   "operation": "CREATE",
                                   "name": f"p{i}", "namespace": ns,
                                   "userInfo": {"username": "t@ns"},
                                   "object": _ns_pod(i, ns)}})
    rec = flightrec.FlightRecorder(capacity=64, sink_path=sink,
                                   capture=True)
    denies = 0
    with flightrec.activate(rec):
        for b in bodies:
            resp = handler.handle(b)
            denies += 0 if resp.allowed else 1
    rec.close()
    gc = getattr(runtime.driver, "gen_coord", None)
    if gc is not None:
        gc.stop()
    records, _counts = core.read_corpus(sink)
    assert denies == 6 and len(records) == 12
    # spill the recorded cluster (Namespaces included) as rows
    root = str(tmp_path_factory.mktemp("ns-spill"))
    audit_rt = core.load_candidate([NS_AUDIT_TEMPLATE,
                                    NS_AUDIT_CONSTRAINT])
    evaluator = ShardedEvaluator(audit_rt.driver, make_mesh(),
                                 violations_limit=20)
    cluster = FakeCluster()
    for o in list(ns_live.values()) + [_ns_pod(i, "alpha")
                                       for i in (90, 91)]:
        cluster.apply(copy.deepcopy(o))
    snap = ClusterSnapshot(evaluator, SnapshotConfig())
    mgr = AuditManager(
        audit_rt.client, lister=lambda: iter(cluster.list()),
        config=AuditConfig(audit_source="snapshot", chunk_size=64,
                           exact_totals=False, pipeline="off"),
        evaluator=evaluator, snapshot=snap)
    mgr.audit()
    wrote = SnapshotSpill(root).save(
        snap, templates=templates_digest(audit_rt.client))
    assert wrote["ok"]
    gc = getattr(audit_rt.driver, "gen_coord", None)
    if gc is not None:
        gc.stop()
    return {"records": records, "sink": sink, "root": root}


def test_namespaces_from_spill_extracts_recorded_fixtures(ns_corpus):
    ns = core.namespaces_from_spill(core.read_spill(ns_corpus["root"]))
    assert set(ns) == {"alpha", "beta"}
    assert ns["alpha"]["metadata"]["labels"] == {"team": "a"}


def test_namespace_selector_replay_pins_recorded_labels(ns_corpus):
    """Stale candidate Namespace fixtures flip namespace-selector
    verdicts (looks like a library change, is corpus skew); sourcing
    fixtures from the recorded spill restores bit-identity."""
    stale = [NS_SEL_TEMPLATE, NS_SEL_CONSTRAINT,
             _ns_doc("alpha", "b"), _ns_doc("beta", "b")]

    def run(**kw):
        rt = core.load_candidate(stale, **kw)
        try:
            return core.replay_decisions(ns_corpus["records"], rt,
                                         differential=True)
        finally:
            gc = getattr(rt.driver, "gen_coord", None)
            if gc is not None:
                gc.stop()

    skewed = run()
    assert not skewed["differential"]["bit_identical"]
    assert skewed["newly_allowed"] == 6  # every alpha deny flipped
    fixed = run(namespaces=core.namespaces_from_spill(
        core.read_spill(ns_corpus["root"])))
    assert fixed["differential"]["bit_identical"]
    assert fixed["newly_allowed"] == 0


def test_replay_cli_namespaces_from_spill_flag(ns_corpus, tmp_path,
                                               capsys):
    """--namespaces-from-spill: opt-in; without it the stale-fixture
    skew exits 1, with it the same corpus is bit-identical (exit 0)."""
    f = _docs_file(tmp_path, [NS_SEL_TEMPLATE, NS_SEL_CONSTRAINT,
                              _ns_doc("alpha", "b"),
                              _ns_doc("beta", "b")], "ns-cand.json")
    base = ["-f", ns_corpus["sink"], "--candidate", f,
            "--differential", "-o", "json"]
    assert replay_cmd.run_cli(base) == 1
    capsys.readouterr()
    rc = replay_cmd.run_cli(base + ["--namespaces-from-spill",
                                    ns_corpus["root"]])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["differential"]["bit_identical"]
