"""Per-tenant, per-priority admission QoS (ISSUE 10).

Acceptance pins:
- priority lanes preempt: system/break-glass traffic dequeues ahead of
  user lanes and sheds last;
- weighted-fair (deficit-round-robin) dequeue holds tenant weights in
  COST units under skewed object sizes;
- per-tenant inflight caps and queue-cost budgets hold;
- tenant-aware displacement sheds the heaviest tenant first, never the
  mid-burst arrival by default;
- identical (config, seed, arrival order) replays the exact
  dequeue/shed trajectory;
- multi-tenant isolation chaos: tenant A at 8x offered load plus an
  injected ``webhook.overload`` fault must not move tenant B's accepted
  P99 beyond 2x unloaded, and drain answers every accepted uid across
  all lanes;
- ``--qos off`` (the compat default) is bit-identical to the PR 5
  single-FIFO path over the library corpus — pinned in
  ``tests/test_overload.py::test_qos_off_bit_identical_to_pr5_fifo_
  over_library`` (it shares that module's library fixture instead of
  building a second client).
"""

import http.client
import json
import threading
import time

import pytest

from gatekeeper_tpu.metrics import registry as M
from gatekeeper_tpu.metrics.registry import MetricsRegistry
from gatekeeper_tpu.observability import costattr, flightrec
from gatekeeper_tpu.resilience import overload as ovl
from gatekeeper_tpu.resilience import qos
from gatekeeper_tpu.resilience.faults import FaultPlan, inject
from gatekeeper_tpu.webhook.policy import ValidationHandler
from gatekeeper_tpu.webhook.server import WebhookServer
from tests.traffic_helpers import drive_tenant_mix


class _EmptyResponses:
    stats_entries: list = []

    def results(self):
        return []


class _TenantTrackingClient:
    """Review stub recording per-namespace review concurrency (the
    inflight-cap witness) with a configurable service time."""

    drivers: list = []

    def __init__(self, service_s: float = 0.0):
        self.service_s = service_s
        self.reviews = 0
        self.max_conc: dict = {}
        self._cur: dict = {}
        self._lock = threading.Lock()

    def constraints(self):
        return []

    def review(self, augmented, **kw):
        ns = augmented.admission_request.namespace or "_cluster"
        with self._lock:
            self.reviews += 1
            self._cur[ns] = self._cur.get(ns, 0) + 1
            if self._cur[ns] > self.max_conc.get(ns, 0):
                self.max_conc[ns] = self._cur[ns]
        try:
            if self.service_s:
                time.sleep(self.service_s)
            return _EmptyResponses()
        finally:
            with self._lock:
                self._cur[ns] -= 1


def _body(uid="u1", namespace="team-a", username="load", kind="Pod",
          nbytes=0):
    obj = {"apiVersion": "v1", "kind": kind,
           "metadata": {"name": "x", "namespace": namespace}}
    if nbytes:
        obj["data"] = "x" * nbytes
    return {
        "apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
        "request": {"uid": uid, "operation": "CREATE",
                    "kind": {"group": "", "version": "v1", "kind": kind},
                    "namespace": namespace,
                    "userInfo": {"username": username},
                    "object": obj},
    }


def _lv(cfg, name):
    return next(lv for lv in cfg.levels if lv.name == name)


# --- config parsing / routing ---------------------------------------------

def test_qos_config_parse_and_classify(tmp_path):
    doc = {
        "tenantKey": "namespace",
        "priorityLevels": [
            {"name": "system", "matchNamespaces": ["kube-system"],
             "matchUserPrefixes": ["system:node:"]},
            {"name": "break-glass",
             "matchNamespacePrefixes": ["break-glass"]},
            {"name": "user"},
        ],
        "tenantWeights": {"team-a": 4},
        "defaultTenantWeight": 1,
        "tenantInflightCap": 8,
        "tenantQueueCost": 64e6,
        "quantum": 4096,
    }
    p = tmp_path / "qos.json"
    p.write_text(json.dumps(doc))
    cfg = qos.load_qos_config(str(p))
    assert [lv.name for lv in cfg.levels] == ["system", "break-glass",
                                              "user"]
    assert cfg.classify("kube-system", "").name == "system"
    assert cfg.classify("anything", "system:node:n1").name == "system"
    assert cfg.classify("break-glass-ops", "").name == "break-glass"
    assert cfg.classify("team-a", "alice").name == "user"
    assert cfg.weight("team-a") == 4 and cfg.weight("team-b") == 1
    assert cfg.tenant_inflight_cap == 8
    # tenant keys
    req = {"namespace": "team-a", "userInfo": {"username": "alice"}}
    assert qos.tenant_of_request(req) == "team-a"
    assert qos.tenant_of_request(req, "serviceaccount") == "alice"
    assert qos.tenant_of_request({}, "namespace") == qos.CLUSTER_TENANT
    with pytest.raises(ValueError):
        qos.parse_qos_config({"tenantKey": "nope"})
    # --qos off (the compat default) yields no config at all
    assert qos.qos_from_args("off", str(p)) is None
    assert qos.qos_from_args("on", str(p)).tenant_inflight_cap == 8


# --- the DRR queue (deterministic, driven directly) -----------------------

def test_drr_weights_hold_under_skewed_object_sizes():
    """Tenant A posts 16x bigger objects than B at equal weight: served
    COST stays ~equal (request counts skew instead) — the fairness unit
    is cost, not request slots.  With weight 2, B earns ~2x the cost
    share."""
    for w_b, want_ratio in ((1.0, 1.0), (2.0, 2.0)):
        cfg = qos.QoSConfig(quantum=1000.0,
                            tenant_weights={"b": w_b})
        q = qos.QoSQueue(cfg)
        lv = _lv(cfg, "user")
        seq = 0
        for i in range(64):
            q.enqueue(qos.Ticket(seq, "a", lv, 16000.0), 1000, 1e18)
            seq += 1
        for i in range(1024):
            q.enqueue(qos.Ticket(seq, "b", lv, 1000.0), 1000, 1e18)
            seq += 1
        served = {"a": 0.0, "b": 0.0}
        for _ in range(200):
            t = q.pick_next(lambda tn: 0)
            if t is None:
                break
            served[t.tenant] += t.cost
        assert served["a"] > 0 and served["b"] > 0
        ratio = served["b"] / served["a"]
        assert want_ratio / 1.6 <= ratio <= want_ratio * 1.6, \
            f"weight {w_b}: served cost ratio {ratio:.2f}"


def test_priority_lane_strictly_preempts_user_lane():
    cfg = qos.QoSConfig()
    q = qos.QoSQueue(cfg)
    user, system = _lv(cfg, "user"), _lv(cfg, "system")
    q.enqueue(qos.Ticket(0, "team-a", user, 10.0), 1000, 1e18)
    q.enqueue(qos.Ticket(1, "team-b", user, 10.0), 1000, 1e18)
    q.enqueue(qos.Ticket(2, "kube-system", system, 10.0), 1000, 1e18)
    order = [q.pick_next(lambda tn: 0).tenant for _ in range(3)]
    assert order[0] == "kube-system"  # arrived last, dequeues first
    assert set(order[1:]) == {"team-a", "team-b"}


def test_displacement_sheds_heaviest_tenant_first_system_last():
    cfg = qos.QoSConfig()
    heavy = {"whale": 1e9, "minnow": 1.0, "kube-system": 5e9}
    q = qos.QoSQueue(cfg, heaviness=lambda tn: heavy.get(tn, 0.0))
    user, system = _lv(cfg, "user"), _lv(cfg, "system")
    whale_tickets = [qos.Ticket(i, "whale", user, 10.0)
                     for i in range(3)]
    for t in whale_tickets:
        assert q.enqueue(t, 4, 1e18) == (True, None, "")
    sys_t = qos.Ticket(3, "kube-system", system, 10.0)
    assert q.enqueue(sys_t, 4, 1e18) == (True, None, "")
    # queue full (depth 4): a light user tenant displaces the WHALE's
    # newest ticket, not the system lane, not itself
    minnow = qos.Ticket(4, "minnow", user, 10.0)
    admitted, victim, reason = q.enqueue(minnow, 4, 1e18)
    assert admitted and victim is whale_tickets[-1]
    assert victim.shed == "displaced"
    # another whale arrival cannot displace anyone (it IS the heaviest)
    whale_new = qos.Ticket(5, "whale", user, 10.0)
    admitted, victim, reason = q.enqueue(whale_new, 4, 1e18)
    assert not admitted and victim is None and reason == "queue_full"
    # drain everything queued, then fill with system-only traffic: a
    # user arrival must NOT displace system tickets (system sheds last)
    while q.pick_next(lambda tn: 0) is not None:
        pass
    q.enqueue(qos.Ticket(6, "kube-system", system, 10.0), 1000, 1e18)
    q.enqueue(qos.Ticket(7, "kube-system", system, 10.0), 1000, 1e18)
    q.enqueue(qos.Ticket(8, "kube-system", system, 10.0), 1000, 1e18)
    late_user = qos.Ticket(9, "minnow", user, 10.0)
    admitted, victim, reason = q.enqueue(late_user, 3, 1e18)
    assert not admitted and victim is None and reason == "queue_full"
    # ...while a SYSTEM arrival displaces nothing either (same level,
    # not lighter than the heaviest system tenant = itself)
    late_sys = qos.Ticket(10, "kube-system", system, 10.0)
    admitted, victim, _ = q.enqueue(late_sys, 3, 1e18)
    assert not admitted and victim is None


def test_tenant_queue_cost_budget_sheds_only_the_offender():
    cfg = qos.QoSConfig(tenant_queue_cost=100.0)
    q = qos.QoSQueue(cfg)
    user = _lv(cfg, "user")
    assert q.enqueue(qos.Ticket(0, "a", user, 60.0), 1000, 1e18)[0]
    # a's second ticket would exceed ITS budget: shed with the tenant
    # reason, global bounds untouched
    admitted, victim, reason = q.enqueue(qos.Ticket(1, "a", user, 60.0),
                                         0, 0)
    assert not admitted and reason == "tenant_queue_cost"
    # tenant b is unaffected
    assert q.enqueue(qos.Ticket(2, "b", user, 60.0), 1000, 1e18)[0]


def test_pick_next_skips_tenants_at_inflight_cap():
    cfg = qos.QoSConfig(tenant_inflight_cap=1)
    q = qos.QoSQueue(cfg)
    user = _lv(cfg, "user")
    q.enqueue(qos.Ticket(0, "a", user, 10.0), 1000, 1e18)
    q.enqueue(qos.Ticket(1, "b", user, 10.0), 1000, 1e18)
    inflight = {"a": 1}
    t = q.pick_next(lambda tn: inflight.get(tn, 0))
    assert t.tenant == "b"  # a is at cap: skipped, not starved-forever
    # b now at cap too; a still capped: nothing serviceable
    inflight["b"] = 1
    assert q.pick_next(lambda tn: inflight.get(tn, 0)) is None
    # a releases: its queued ticket is served
    inflight["a"] = 0
    assert q.pick_next(lambda tn: inflight.get(tn, 0)).tenant == "a"


def test_seeded_trajectory_replays_exactly():
    """Identical (config, arrival order, release order) => identical
    grant/shed trajectory, twice over — the deterministic-replay pin."""

    def run():
        cfg = qos.QoSConfig(tenant_inflight_cap=2, quantum=512.0,
                            tenant_weights={"team-b": 2})
        ctl = ovl.OverloadController(ovl.OverloadConfig(
            min_inflight=2, max_inflight=2, initial_inflight=2,
            queue_depth=4, queue_timeout_s=5.0, qos=cfg))
        user = _lv(cfg, "user")
        system = _lv(cfg, "system")
        script = [("team-a", user, 4096.0), ("team-a", user, 4096.0),
                  ("team-a", user, 8192.0), ("team-b", user, 512.0),
                  ("kube-system", system, 1024.0),
                  ("team-b", user, 512.0), ("team-a", user, 2048.0)]
        holders: list = []
        # sequential script: each admit runs on its own thread but the
        # ARRIVAL order is serialized by events, and releases happen in
        # scripted order — the trajectory is then a pure function of the
        # config + script
        entered = []

        def one(i, tenant, lv, cost):
            gate = threading.Event()
            holders.append(gate)
            try:
                with ctl.admit(cost, tenant=tenant, priority=lv):
                    entered.append(i)
                    gate.wait(10)
            except ovl.Shed:
                pass

        threads = []
        for i, (tenant, lv, cost) in enumerate(script):
            t = threading.Thread(target=one, args=(i, tenant, lv, cost))
            threads.append(t)
            t.start()
            time.sleep(0.03)  # serialize arrivals
        for gate in list(holders):  # release in arrival order
            gate.set()
            time.sleep(0.03)
        for t in threads:
            t.join(10)
        return list(ctl.trajectory)

    t1, t2 = run(), run()
    assert t1 == t2
    assert any(e[0] == "grant" for e in t1)


# --- controller-level caps + sheds ----------------------------------------

def test_controller_tenant_inflight_cap_holds_under_burst():
    reg = MetricsRegistry()
    cfg = qos.QoSConfig(tenant_inflight_cap=1)
    ctl = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=4, max_inflight=4, initial_inflight=4,
        queue_depth=16, queue_timeout_s=2.0, qos=cfg), metrics=reg)
    client = _TenantTrackingClient(service_s=0.05)
    h = ValidationHandler(client, failure_policy="fail", overload=ctl)
    threads = [threading.Thread(
        target=lambda i=i: h.handle(_body(uid=f"a{i}",
                                          namespace="team-a")))
        for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    # 4 limiter slots but ONE tenant: never more than cap=1 in review
    assert client.max_conc.get("team-a", 0) == 1
    assert client.reviews == 6  # capped, queued, all served (no sheds)
    assert ctl.shed_count == 0


def test_shed_metric_carries_tenant_and_priority_labels():
    reg = MetricsRegistry()
    cfg = qos.QoSConfig()
    ctl = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=1, max_inflight=1, initial_inflight=1,
        queue_depth=0, queue_timeout_s=0.05, qos=cfg), metrics=reg)
    h = ValidationHandler(_TenantTrackingClient(service_s=0.3),
                          failure_policy="fail", overload=ctl)
    held = threading.Event()
    t = threading.Thread(target=lambda: (
        held.set(), h.handle(_body(uid="h", namespace="team-a"))))
    t.start()
    held.wait(2)
    time.sleep(0.05)  # the holder is inside its review
    resp = h.handle(_body(uid="x", namespace="team-b"))
    t.join(5)
    assert resp.code == 429
    assert reg.get_counter(M.OVERLOAD_SHED,
                           {"reason": "queue_full", "tenant": "team-b",
                            "priority": "user"}) == 1


# --- the isolation chaos test ---------------------------------------------

def test_multitenant_isolation_tenant_a_burst_does_not_move_b_p99():
    """THE acceptance pin: tenant A at 8x offered load through a tight
    limiter, plus injected ``webhook.overload`` chaos sheds, must not
    move tenant B's accepted P99 beyond 2x its unloaded P99; the system
    lane sheds last (here: not at all); per-tenant caps hold; excess
    shed cost lands on the attacker."""
    service_s = 0.04
    reg = MetricsRegistry()
    cfg = qos.QoSConfig(tenant_inflight_cap=1, quantum=16384.0)
    # 3 slots, cap 1: each of the three tenants can hold at most one —
    # the attacker's 8x concurrency buys it queueing + sheds, not slots
    ctl = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=3, max_inflight=3, initial_inflight=3,
        queue_depth=6, queue_timeout_s=0.3, qos=cfg), metrics=reg)
    client = _TenantTrackingClient(service_s=service_s)
    h = ValidationHandler(client, failure_policy="fail", overload=ctl)

    # unloaded anchor: sequential tenant-B requests, no contention
    unloaded = []
    for i in range(6):
        t0 = time.perf_counter()
        r = h.handle(_body(uid=f"warm{i}", namespace="tenant-b"))
        assert r.allowed
        unloaded.append(time.perf_counter() - t0)
    unloaded_p99 = sorted(unloaded)[-1]

    plan = FaultPlan([{"site": "webhook.overload", "mode": "error",
                       "after": 10, "every": 9, "times": 3}])
    results: dict = {"tenant-a": [], "tenant-b": [], "kube-system": []}
    sheds: dict = {"tenant-a": 0, "tenant-b": 0, "kube-system": 0}
    lock = threading.Lock()
    stop = threading.Event()

    def closed_loop(ns, n):
        for i in range(n):
            if stop.is_set():
                break
            t0 = time.perf_counter()
            resp = h.handle(_body(uid=f"{ns}-{i}", namespace=ns))
            dt = time.perf_counter() - t0
            with lock:
                if resp.code == 429:
                    sheds[ns] += 1
                else:
                    results[ns].append(dt)

    with inject(plan):
        threads = [threading.Thread(target=closed_loop,
                                    args=("tenant-a", 10))
                   for _ in range(8)]  # 8x offered load
        threads.append(threading.Thread(target=closed_loop,
                                        args=("tenant-b", 12)))
        threads.append(threading.Thread(target=closed_loop,
                                        args=("kube-system", 6)))
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    assert plan.fired("webhook.overload") >= 1  # the chaos actually bit

    assert results["tenant-b"], "tenant B must have accepted requests"
    b_p99 = sorted(results["tenant-b"])[-1]
    assert b_p99 <= 2.0 * unloaded_p99, \
        f"tenant-B P99 {b_p99 * 1e3:.1f}ms vs unloaded " \
        f"{unloaded_p99 * 1e3:.1f}ms: isolation broken"
    # the attacker absorbed the shedding; system lane shed nothing
    # beyond chaos' indiscriminate injections
    assert sheds["tenant-a"] > 0, "an 8x burst through a tight " \
                                  "limiter must shed the attacker"
    queue_sheds_sys = reg.get_counter(
        M.OVERLOAD_SHED, {"reason": "queue_timeout",
                          "tenant": "kube-system", "priority": "system"})
    queue_full_sys = reg.get_counter(
        M.OVERLOAD_SHED, {"reason": "queue_full",
                          "tenant": "kube-system", "priority": "system"})
    assert queue_sheds_sys == 0 and queue_full_sys == 0
    # per-tenant inflight cap held the whole run
    assert client.max_conc.get("tenant-a", 0) <= 1


# --- drain across lanes ----------------------------------------------------

def test_drain_answers_every_accepted_uid_across_all_lanes():
    """Zero-loss drain with QoS on: begin_drain + stop() mid-burst with
    tickets queued across three lanes — every request the server
    accepted is answered with its own uid (grants, sheds and queued
    waiters alike)."""
    reg = MetricsRegistry()
    cfg = qos.QoSConfig(tenant_inflight_cap=2)
    ctl = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=2, max_inflight=2, initial_inflight=2,
        queue_depth=16, queue_timeout_s=5.0, qos=cfg), metrics=reg)
    client = _TenantTrackingClient(service_s=0.06)
    handler = ValidationHandler(client, failure_policy="fail",
                                overload=ctl, metrics=reg)
    accepted: list = []
    accept_lock = threading.Lock()
    inner = handler.handle

    def tracking(body, cost_hint=0):
        with accept_lock:
            accepted.append(body["request"]["uid"])
        return inner(body, cost_hint=cost_hint)

    handler.handle = tracking
    srv = WebhookServer(validation_handler=handler, port=0,
                        metrics=reg).start()
    answered: dict = {}
    failures: list = []
    lock = threading.Lock()
    namespaces = ["tenant-a", "tenant-b", "kube-system",
                  "break-glass-ops"]

    def post(i):
        uid = f"qos-burst-{i}"
        try:
            c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=20)
            c.request("POST", "/v1/admit", json.dumps(
                _body(uid=uid, namespace=namespaces[i % 4])).encode(),
                {"Content-Type": "application/json"})
            doc = json.loads(c.getresponse().read())
            with lock:
                answered[uid] = doc["response"]
            c.close()
        except Exception as e:
            with lock:
                failures.append((uid, e))

    threads = [threading.Thread(target=post, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    time.sleep(0.1)  # burst in flight: slots busy, lanes queued
    drained = srv.stop(drain_timeout=15)
    for t in threads:
        t.join(20)
    assert drained
    accepted_set = set(accepted)
    assert accepted_set, "the burst must have been accepted"
    lost = accepted_set - set(answered)
    assert lost == set(), f"accepted but never answered: {sorted(lost)}"
    for uid in accepted_set:
        assert answered[uid]["uid"] == uid
    assert {u for u, _ in failures} & accepted_set == set()


# --- observability plumbing ------------------------------------------------

def test_flightrec_and_costattr_carry_tenant_axis():
    reg = MetricsRegistry()
    cfg = qos.QoSConfig()
    ctl = ovl.OverloadController(ovl.OverloadConfig(qos=cfg), metrics=reg)
    rec = flightrec.FlightRecorder(capacity=64)
    attr = costattr.CostAttribution(metrics=reg)
    h = ValidationHandler(_TenantTrackingClient(), overload=ctl)
    with flightrec.activate(rec), costattr.activate(attr):
        h.handle(_body(uid="t1", namespace="team-a"))
        h.handle(_body(uid="t2", namespace="team-b"))
        h.handle(_body(uid="t3", namespace="team-a"))
    e = rec.by_uid("t1")[0]
    assert e["tenant"] == "team-a" and e["priority"] == "user"
    # the ?tenant= filter composes like the others
    snap = rec.snapshot(tenant="team-a")
    assert snap["matched"] == 2
    assert all(x["tenant"] == "team-a" for x in snap["decisions"])
    # cost grid: per-tenant admission seconds + the heaviness roll-up
    totals = attr.tenant_totals("webhook")
    assert set(totals) == {"team-a", "team-b"}
    assert totals["team-a"] > 0
    snap = attr.snapshot()
    assert {t["tenant"] for t in snap["tenants"]} == {"team-a", "team-b"}
    # the metric rides {tenant, enforcement_point, phase=admission}
    assert reg.get_counter(M.CONSTRAINT_EVAL,
                           {"tenant": "team-a",
                            "enforcement_point": "webhook",
                            "phase": "admission"}) > 0
    # tenant cells never pollute the per-template closure population
    assert attr.total_seconds("webhook") == 0.0


def test_debug_overload_lane_view_and_decisions_tenant_filter():
    reg = MetricsRegistry()
    cfg = qos.QoSConfig(tenant_inflight_cap=3)
    ctl = ovl.OverloadController(ovl.OverloadConfig(qos=cfg), metrics=reg)
    rec = flightrec.FlightRecorder(capacity=64)
    h = ValidationHandler(_TenantTrackingClient(), overload=ctl)
    srv = WebhookServer(validation_handler=h, port=0, metrics=reg).start()
    try:
        with ovl.activate(ctl), flightrec.activate(rec):
            h.handle(_body(uid="d1", namespace="team-a"))
            h.handle(_body(uid="d2", namespace="team-b"))
            c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                           timeout=5)
            c.request("GET", "/debug/overload")
            doc = json.loads(c.getresponse().read())
            assert doc["mode"] == "qos"
            assert [ln["priority"] for ln in doc["qos"]["lanes"]] == \
                ["system", "break-glass", "user"]
            assert doc["qos"]["tenant_inflight_cap"] == 3
            assert doc["qos"]["trajectory_len"] >= 2
            c.request("GET", "/debug/decisions?tenant=team-b")
            doc = json.loads(c.getresponse().read())
            assert doc["matched"] == 1
            assert doc["decisions"][0]["uid"] == "d2"
            c.close()
    finally:
        srv.stop(drain_timeout=3)


def test_gator_decisions_reader_matches_debug_semantics(tmp_path):
    """The offline reader over the JSONL sink: uid/since/until/decision/
    tenant filters behave exactly like /debug/decisions (half-open
    range, compose), most recent first, malformed lines survive."""
    from gatekeeper_tpu.gator import decisions_cmd

    sink = tmp_path / "decisions.jsonl"
    rec = flightrec.FlightRecorder(capacity=64, sink_path=str(sink),
                                   wall=iter(range(100)).__next__)
    rec.record("validate", "allow", uid="u0", tenant="team-a")
    rec.record("validate", "shed", uid="u1", tenant="team-b",
               reason="queue_full")
    rec.record("validate", "shed", uid="u2", tenant="team-a",
               reason="displaced")
    rec.record("mutate", "deny", uid="u3", tenant="team-a")
    rec.close()
    with open(sink, "a") as f:
        f.write("corrupt line\n")
    doc = decisions_cmd.read_decisions(str(sink), kinds={"shed"},
                                       tenant="team-a")
    assert doc["matched"] == 1 and doc["decisions"][0]["uid"] == "u2"
    assert doc["malformed"] == 1
    # half-open [since, until): ts 1 included, ts 3 excluded
    doc = decisions_cmd.read_decisions(str(sink), since=1, until=3)
    assert [e["uid"] for e in doc["decisions"]] == ["u2", "u1"]
    doc = decisions_cmd.read_decisions(str(sink), uid="u1")
    assert doc["matched"] == 1
    assert doc["decisions"][0]["reason"] == "queue_full"
    # the CLI wrapper end-to-end (in-process)
    rc = decisions_cmd.run_cli(["-f", str(sink), "--decision", "shed",
                                "--tenant", "team-a", "-o", "json"])
    assert rc == 0
    assert decisions_cmd.run_cli(["-f", str(sink), "--since", "bogus"]) \
        == 2


# --- a tenant mix against a live server ------------------------------------

def test_bench_tenant_mix_smoke_toy_scale():
    """A multi-tenant closed-loop mix at toy scale against a live server
    with QoS on: every tenant's requests are answered or shed, none
    errors, and the quiet tenant survives the noisy one."""
    reg = MetricsRegistry()
    cfg = qos.QoSConfig(tenant_inflight_cap=2)
    ctl = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=2, max_inflight=2, initial_inflight=2,
        queue_depth=8, queue_timeout_s=0.2, qos=cfg), metrics=reg)
    h = ValidationHandler(_TenantTrackingClient(service_s=0.01),
                          failure_policy="fail", overload=ctl)
    srv = WebhookServer(validation_handler=h, port=0, metrics=reg).start()
    try:
        bodies = {
            ns: [json.dumps(_body(uid=f"{ns}-{i}",
                                  namespace=ns)).encode()
                 for i in range(8)]
            for ns in ("tenant-a", "tenant-b", "kube-system")}
        anchor = drive_tenant_mix(srv.port, [
            {"name": "tenant-b", "conc": 1, "n": 6}], bodies)
        mix = drive_tenant_mix(srv.port, [
            {"name": "tenant-a", "conc": 6, "n": 24},
            {"name": "tenant-b", "conc": 1, "n": 6},
            {"name": "kube-system", "conc": 1, "n": 4},
        ], bodies)
        assert set(mix) == {"tenant-a", "tenant-b", "kube-system"}
        for st in mix.values():
            assert st["requests"] == st["accepted"] + st["shed"]
            assert not st["errors"]
        assert anchor["tenant-b"]["p99_ms"] > 0
        assert mix["tenant-b"]["accepted"] > 0  # B survived the mix
    finally:
        srv.stop(drain_timeout=3)


# --- PR 11 QoS hardening: SA-triple normalization + AIMD-derived cap ------

def test_serviceaccount_tenant_normalization():
    """The serviceaccount tenant key must not trust userInfo.username
    verbatim: only a well-formed system:serviceaccount:<ns>:<name>
    triple normalizes; malformed/spoof-shaped identities fold into the
    cluster tenant instead of minting themselves a fair-share queue."""
    def t(username):
        return qos.tenant_of_request(
            {"namespace": "x", "userInfo": {"username": username}},
            qos.TENANT_SERVICEACCOUNT)

    assert t("system:serviceaccount:team-a:bot") == \
        "system:serviceaccount:team-a:bot"
    # extra segments, empty parts, whitespace, case games: NOT an SA
    assert t("system:serviceaccount:team-a:bot:extra") == \
        qos.CLUSTER_TENANT
    assert t("system:serviceaccount::bot") == qos.CLUSTER_TENANT
    assert t("system:serviceaccount:team-a:") == qos.CLUSTER_TENANT
    assert t("system:serviceaccount: team-a :bot") == qos.CLUSTER_TENANT
    assert t("System:ServiceAccount:team-a:bot") == qos.CLUSTER_TENANT
    # non-SA identities keep their username; empty folds to cluster
    assert t("alice") == "alice"
    assert t("") == qos.CLUSTER_TENANT
    # the unit normalizer agrees
    assert qos.normalize_serviceaccount(
        "system:serviceaccount:a:b") == "system:serviceaccount:a:b"
    assert qos.normalize_serviceaccount("system:serviceaccount:a") is None


def test_tenant_cap_derives_from_live_aimd_limit():
    """tenantInflightCap scales with the limiter's LIVE limit: a cap
    chosen as a fraction of healthy capacity keeps that fraction when
    AIMD collapses, so one tenant can never own every remaining slot
    (the PR 10 isolation guarantee surviving limit collapse)."""
    cfg = qos.QoSConfig(tenant_inflight_cap=4)
    ctl = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=1, max_inflight=8, initial_inflight=8,
        queue_depth=16, queue_timeout_s=2.0, qos=cfg))
    assert ctl._tenant_cap() == 4  # healthy: the configured cap
    with ctl.limiter._lock:
        ctl.limiter._limit = 2.0  # AIMD collapse
    assert ctl._tenant_cap() == 1  # ceil(4 * 2/8) = 1: a slot stays free
    with ctl.limiter._lock:
        ctl.limiter._limit = 4.0
    assert ctl._tenant_cap() == 2
    # snapshot surfaces the cap in force
    assert ctl._queue_qos.snapshot()["tenant_inflight_cap"] == 2
    # cap 0 stays unbounded at any limit
    cfg0 = qos.QoSConfig()
    ctl0 = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=1, max_inflight=8, initial_inflight=2, qos=cfg0))
    assert ctl0._tenant_cap() == 0


def test_collapsed_limit_tenant_cannot_hoard_slots():
    """Behavioral pin: static cap 4, limit collapsed to 2 — tenant A's
    burst must never hold more than the DERIVED cap (1) in review, so
    a victim tenant still gets the other slot."""
    cfg = qos.QoSConfig(tenant_inflight_cap=4)
    ctl = ovl.OverloadController(ovl.OverloadConfig(
        min_inflight=1, max_inflight=8, initial_inflight=8,
        queue_depth=32, queue_timeout_s=2.0, qos=cfg))
    with ctl.limiter._lock:
        ctl.limiter._limit = 2.0
    client = _TenantTrackingClient(service_s=0.05)
    h = ValidationHandler(client, failure_policy="fail", overload=ctl)
    threads = [threading.Thread(
        target=lambda i=i, ns=ns: h.handle(
            _body(uid=f"{ns}-{i}", namespace=ns)))
        for ns in ("team-a", "team-b") for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert client.max_conc.get("team-a", 0) <= 1
    assert client.max_conc.get("team-b", 0) <= 1
    assert client.reviews == 8
    assert ctl.shed_count == 0


# --- demand-aware assuredConcurrencyShares (PR 12) -------------------------

def _shares_cfg():
    return qos.parse_qos_config({
        "priorityLevels": [
            {"name": "system", "matchNamespaces": ["kube-system"],
             "assuredConcurrencyShares": 1},
            {"name": "user", "assuredConcurrencyShares": 3},
        ]})


def test_shares_parse_and_snapshot():
    cfg = _shares_cfg()
    assert _lv(cfg, "system").shares == 1
    assert _lv(cfg, "user").shares == 3
    q = qos.QoSQueue(cfg)
    assert q.assured_cap(_lv(cfg, "system"), 8) == 2   # ceil(8*1/4)
    assert q.assured_cap(_lv(cfg, "user"), 8) == 6
    snap = q.snapshot()
    assert {l["priority"]: l["shares"] for l in snap["lanes"]} == \
        {"system": 1, "user": 3}


def test_shares_bound_a_system_lane_flood():
    """A pathological system-lane flood is bounded: with user demand
    queued, the system lane cannot take slots past its assured
    concurrency — user traffic keeps its share instead of starving
    under strict priority."""
    cfg = _shares_cfg()
    q = qos.QoSQueue(cfg)
    system, user = _lv(cfg, "system"), _lv(cfg, "user")
    seq = 0
    for i in range(32):  # the flood
        q.enqueue(qos.Ticket(seq, "kube-system", system, 10.0), 1000, 1e18)
        seq += 1
    for i in range(8):
        q.enqueue(qos.Ticket(seq, "team-a", user, 10.0), 1000, 1e18)
        seq += 1
    limit = 8
    lane_inflight = {"system": 0, "user": 0}
    granted = []
    for _ in range(limit):  # fill every limiter slot
        t = q.pick_next(lambda tn: 0,
                        lane_inflight_of=lambda nm: lane_inflight[nm],
                        limit=limit)
        assert t is not None
        lane_inflight[t.level.name] += 1
        granted.append(t.level.name)
    # system bounded at ceil(8 * 1/4) = 2; user holds its 6
    assert lane_inflight == {"system": 2, "user": 6}, granted


def test_shares_work_conserving_without_lower_demand():
    """With NO lower-priority demand the cap does not idle slots: the
    system lane takes everything (the second work-conserving pass)."""
    cfg = _shares_cfg()
    q = qos.QoSQueue(cfg)
    system = _lv(cfg, "system")
    for i in range(8):
        q.enqueue(qos.Ticket(i, "kube-system", system, 10.0), 1000, 1e18)
    lane_inflight = {"system": 0, "user": 0}
    for _ in range(8):
        t = q.pick_next(lambda tn: 0,
                        lane_inflight_of=lambda nm: lane_inflight[nm],
                        limit=8)
        assert t is not None
        lane_inflight[t.level.name] += 1
    assert lane_inflight["system"] == 8  # nothing below wanted the slots


def test_shares_unset_keeps_strict_priority_bit_identical():
    """All-zero shares (the default): pick_next with the new arguments
    decides exactly what the legacy call decides."""
    def fill(q, cfg):
        user, system = _lv(cfg, "user"), _lv(cfg, "system")
        seq = 0
        for tn, lv in (("team-a", user), ("kube-system", system),
                       ("team-b", user), ("kube-system", system)):
            q.enqueue(qos.Ticket(seq, tn, lv, 10.0), 1000, 1e18)
            seq += 1

    cfg = qos.QoSConfig()
    q1, q2 = qos.QoSQueue(cfg), qos.QoSQueue(cfg)
    fill(q1, cfg)
    fill(q2, cfg)
    legacy = [q1.pick_next(lambda tn: 0).tenant for _ in range(4)]
    shares = [q2.pick_next(lambda tn: 0,
                           lane_inflight_of=lambda nm: 0,
                           limit=8).tenant for _ in range(4)]
    assert legacy == shares
