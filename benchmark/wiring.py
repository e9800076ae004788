"""The objects ``python -m gatekeeper_tpu`` wires, wired the same way.

``gatekeeper_tpu/__main__.py:main`` is one function with nothing to call, so
each piece here mirrors the lines of it named beside it (README.md has the
table).  Every constructor argument is ``__main__``'s default unless the
configuration file lists it under ``assumed``.  Nothing here pumps a
batcher, pins a lane or sets a flag of the program.
"""

from __future__ import annotations

import os

LIBRARY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "library")


def template_dirs(config: dict) -> list:
    lib = config["library"]
    if lib["templates"] != "all":
        return [os.path.join(LIBRARY, t) for t in lib["templates"]]
    return sorted(
        os.path.join(LIBRARY, area, name) for area in lib["areas"]
        for name in os.listdir(os.path.join(LIBRARY, area))
        if os.path.exists(os.path.join(LIBRARY, area, name, "template.yaml")))


def load_library(client, config: dict) -> tuple:
    """Each template of the configuration with its samples/constraint.yaml,
    as ``utils/synthetic.load_library`` adds the whole library.  Returns
    (templates, constraints) and holds them to the configuration's count."""
    from gatekeeper_tpu.utils.unstructured import load_yaml_file

    nt = nc = 0
    for d in template_dirs(config):
        client.add_template(load_yaml_file(
            os.path.join(d, "template.yaml"))[0])
        nt += 1
        for doc in load_yaml_file(
                os.path.join(d, "samples", "constraint.yaml")):
            client.add_constraint(doc)
            nc += 1
    want = config["library"]["expect"]
    if (nt, nc) != (want["templates"], want["constraints"]):
        raise RuntimeError(f"{config['name']}: loaded {nt} templates and "
                           f"{nc} constraints, the file says {want}")
    return nt, nc


def interpreter_client(config: dict):
    """The plain reference: the exact interpreter alone (RegoDriver +
    CELDriver), no TpuDriver, no JAX."""
    from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.drivers.cel_driver import CELDriver
    from gatekeeper_tpu.drivers.rego_driver import RegoDriver
    from gatekeeper_tpu.target.target import K8sValidationTarget

    client = Client(target=K8sValidationTarget(),
                    drivers=[RegoDriver(), CELDriver()],
                    enforcement_points=[WEBHOOK_EP, AUDIT_EP])
    load_library(client, config)
    return client


class Program:
    """The system under test: what ``main()`` builds before it branches
    into audit and serving."""

    def __init__(self, config: dict, traced: bool, seed: int,
                 chips: int = 1):
        from gatekeeper_tpu.apis.constraints import AUDIT_EP, WEBHOOK_EP
        from gatekeeper_tpu.client.client import Client
        from gatekeeper_tpu.drivers.cel_driver import CELDriver
        from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
        from gatekeeper_tpu.metrics.registry import MetricsRegistry
        from gatekeeper_tpu.observability import costattr, flightrec, slo
        from gatekeeper_tpu.observability import tracing
        from gatekeeper_tpu.resilience import overload
        from gatekeeper_tpu.resilience.qos import qos_from_args
        from gatekeeper_tpu.target.target import K8sValidationTarget
        from gatekeeper_tpu.utils.xla_cache import configure_xla_cache

        self.config = config
        self.chips = chips
        self.metrics = MetricsRegistry()                    # :607
        self.tracer = None
        if traced:                                          # :609-619 --trace
            self.tracer = tracing.Tracer(seed=seed, ring_capacity=1 << 18,
                                         metrics=self.metrics)
            tracing.install(self.tracer)
        self.overload = overload.OverloadController(        # :638-650
            overload.OverloadConfig(
                max_inflight=64, queue_depth=256, queue_cost=256e6,
                qos=qos_from_args("off", "")),
            metrics=self.metrics)
        overload.install(self.overload)
        self.cost_attr = costattr.CostAttribution(          # :665-668
            metrics=self.metrics)
        costattr.install(self.cost_attr)
        self.flight_rec = flightrec.FlightRecorder(         # :675-684
            capacity=2048, sink_path=None, metrics=self.metrics,
            capture=False, sink_max_bytes=0, sink_keep=3)
        flightrec.install(self.flight_rec)
        self.slo = slo.SLOEngine(self.metrics,              # :714-718
                                 brownout=self.overload, degradations=None)
        self.slo.start(interval_s=10.0)
        self.xla_cache_dir = configure_xla_cache()          # :752-754
        cel = CELDriver()                                   # :734
        self.tpu = TpuDriver(cel_driver=cel, metrics=self.metrics,  # :762
                             generation_swap=True, compile_cache=None)
        self.client = Client(target=K8sValidationTarget(),  # :765-767
                             drivers=[self.tpu, cel],
                             enforcement_points=[WEBHOOK_EP, AUDIT_EP])
        self.tpu.gen_coord.constraints_fn = self.client.constraints  # :770
        load_library(self.client, config)
        fallback = self.tpu.fallback_kinds()
        if len(fallback) != config["library"]["expect"][
                "on_interpreter_fallback"]:
            raise RuntimeError(f"templates on the interpreter fallback: "
                               f"{fallback}")
        self.evaluator = None
        self.batcher = self.server = None

    def sync_inventory(self, objects) -> int:
        """Referential inventory (``data.inventory``), as the sync
        controller feeds it."""
        n = 0
        for obj in objects:
            self.client.add_data(obj)
            n += 1
        return n

    def build_audit(self, lister):
        """An audit plane over ``lister``; every one shares the program's
        one evaluator, and so its compiled sweep programs."""
        from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
        from gatekeeper_tpu.parallel.sharded import (ShardedEvaluator,
                                                     make_mesh)

        a = self.config["audit"]
        if self.evaluator is None:
            # what the configuration defines and nothing else: every lane
            # option (flatten lane, collect, workers, pipeline, shard
            # chunks, audit source) is the constructor's own default, so a
            # PR that deletes one need not edit this file
            # (tests/benchmark/test_wiring_defaults.py)
            self.evaluator = ShardedEvaluator(              # :883-889
                self.tpu, make_mesh(self.chips),
                violations_limit=a["violations_limit"],
                metrics=self.metrics)
        return AuditManager(                                # :1005-1029
            self.client, lister=lister,
            config=AuditConfig(
                interval_s=60.0, violations_limit=a["violations_limit"],
                chunk_size=a["chunk_size"],
                exact_totals=a["exact_totals"]),
            evaluator=self.evaluator, metrics=self.metrics)

    def build_serving(self, namespace_objects: dict):
        from gatekeeper_tpu.webhook.policy import Batcher, ValidationHandler
        from gatekeeper_tpu.webhook.server import WebhookServer

        self.batcher = Batcher(self.client, stats=False,    # :1113-1115
                               small_batch=None,
                               metrics=self.metrics).start()
        handler = ValidationHandler(                        # :1213-1230
            self.client, namespace_lookup=namespace_objects.get,
            batcher=self.batcher, log_denies=False, metrics=self.metrics,
            fail_open=False, failure_policy="fail", deadline_budget_s=0.0,
            log_stats=False, overload=self.overload)
        self.server = WebhookServer(                        # :1209-1253
            validation_handler=handler, port=0, metrics=self.metrics,
            readiness_check=lambda: True, backlog=128,
            batcher=self.batcher, cost_attribution=self.cost_attr,
            slo_engine=self.slo, flight_recorder=self.flight_rec).start()
        return self.server

    def spans(self, since: float, until: float = float("inf")) -> list:
        """The program's spans that started in [since, until) of the wall
        clock (traced runs)."""
        return [sp for tr in self.tracer.traces() for sp in tr["spans"]
                if since <= sp["start_ts"] < until]

    def begin_background_compile(self) -> None:
        """Boot is over: template churn would compile in the background
        from here on (:1277, controller/manager.py:129)."""
        self.tpu.gen_coord.start()

    def close(self) -> None:
        from gatekeeper_tpu.observability import costattr, flightrec
        from gatekeeper_tpu.observability import tracing
        from gatekeeper_tpu.resilience import overload

        if self.server is not None:
            self.server.stop(drain_timeout=10.0)            # :1325
        if self.batcher is not None:
            self.batcher.stop()                             # :1330
        self.tpu.gen_coord.stop()                           # :1345
        self.slo.stop()                                     # :1354
        self.flight_rec.close()                             # :1356
        overload.uninstall()
        costattr.uninstall()
        flightrec.uninstall()
        tracing.uninstall()
