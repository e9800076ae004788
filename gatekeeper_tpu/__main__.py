"""Process entry: run the framework against a manifest directory.

Reference: main.go — two deployment shapes share one binary, split by
--operation (audit pod vs controller-manager/webhook pod,
deploy/gatekeeper.yaml:5744,5852).  This entry reconciles manifests from
--manifests into the systems, then serves the webhook and/or runs the audit
loop:

    python -m gatekeeper_tpu --manifests ./manifests \
        --operation webhook --operation audit --port 8443
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="gatekeeper-tpu")
    p.add_argument("--manifests", action="append", default=[],
                   help="directory/file of templates, constraints, config, "
                        "mutators, data objects")
    p.add_argument("--kubeconfig", default="",
                   help="run against a live Kubernetes apiserver (watch + "
                        "paged list informer plane); 'in-cluster' uses the "
                        "service-account environment")
    p.add_argument("--evaluate-sidecar", default="",
                   help="host:port of a device-owning Evaluate sidecar "
                        "(python -m gatekeeper_tpu.rpc.sidecar); this "
                        "process then runs the control plane only — no "
                        "local accelerator")
    p.add_argument("--operation", action="append", default=[],
                   help="audit|webhook|mutation-webhook (repeatable; "
                        "default all)")
    p.add_argument("--port", type=int, default=8443)
    p.add_argument("--readiness-retries", type=int, default=0,
                   help="ingestion attempts allowed before a failing "
                        "resource's readiness expectation is cancelled; "
                        "-1 retries indefinitely (reference "
                        "--readiness-retries, object_tracker.go:36)")
    p.add_argument("--audit-interval", type=float, default=60.0)
    p.add_argument("--constraint-violations-limit", type=int, default=20)
    p.add_argument("--audit-chunk-size", type=int, default=500)
    p.add_argument("--audit-source", default="relist",
                   choices=["relist", "snapshot"],
                   help="sweep input: 'relist' pages the cluster every "
                        "pass; 'snapshot' keeps the flattened columns "
                        "RESIDENT between sweeps, maintained by the "
                        "watch seam — a full pass evaluates resident "
                        "columns (no list/flatten cost) and interval "
                        "ticks evaluate only the watch-dirtied rows "
                        "(O(churn)); a periodic full-resync "
                        "differential asserts snapshot == fresh relist "
                        "bit-identical (README 'Incremental audit & "
                        "snapshot')")
    p.add_argument("--snapshot-resync-every", type=int, default=10,
                   help="snapshot mode: every Nth audit interval runs "
                        "the full-resync differential instead of an "
                        "incremental tick (0 = never); divergence "
                        "marks the run incomplete and rebuilds the "
                        "snapshot")
    p.add_argument("--snapshot-resync-rotate", type=int, default=0,
                   help="rotate the resync differential over 1/K of "
                        "the keyspace per resync interval: each resync "
                        "re-flattens only its deterministic key-hash "
                        "slice, so the bit-identity proof amortizes "
                        "(K consecutive resyncs cover every row) "
                        "instead of re-flattening the whole cluster in "
                        "one generation; 0/1 = off (one-shot full "
                        "differential incl. the cluster-global verdict "
                        "check)")
    p.add_argument("--snapshot-spill", default="",
                   help="snapshot mode: directory for the on-disk spill "
                        "of the resident audit state (tall columns + "
                        "vocab + row ids + verdicts + per-GVK rv marks). "
                        "On boot a valid spill warm-starts the auditor — "
                        "watches resubscribe FROM the recorded rv and "
                        "the first tick pays zero relist and zero "
                        "flatten; a corrupt or drifted spill is deleted "
                        "and the boot relists (README 'Cold start & "
                        "persistence').  Spills write off the audit "
                        "thread after each clean resync and at drain")
    p.add_argument("--snapshot-spill-compress", default="none",
                   choices=["none", "zlib"],
                   help="spill section codec: 'none' (bit-identical to "
                        "the uncompressed format — right for 1-core "
                        "hosts, where zlib CPU costs more than the "
                        "bytes) or 'zlib' (NVMe-rich hosts: ~3-5x "
                        "smaller sections for one compress pass on the "
                        "spill worker).  The header records the codec; "
                        "the loader auto-detects either, so flipping "
                        "the flag never strands an existing spill")
    p.add_argument("--snapshot-spill-delta", action="store_true",
                   help="incremental spills: groups split into per-group "
                        "section files and a spill rewrites ONLY the "
                        "groups whose mutation mark moved since the last "
                        "write — O(churn) disk instead of O(cluster). "
                        "Every --snapshot-spill-full-every'th spill is a "
                        "full rewrite that prunes orphaned group files "
                        "(the compaction path); off keeps the inline "
                        "single-section format byte-identical")
    p.add_argument("--snapshot-spill-full-every", type=int, default=8,
                   help="delta spills: force a full rewrite (and orphan "
                        "prune) every Nth spill (default 8)")
    p.add_argument("--snapshot-residency", default="auto",
                   choices=["auto", "on", "off"],
                   help="device-resident snapshot columns: keep each "
                        "group's tall packed columns + match masks in "
                        "device HBM, apply watch patches as device "
                        "scatter from dirty-row slivers, and dispatch "
                        "audit chunks as an index gather — a warm clean "
                        "tick uploads ZERO bytes (README 'Device-"
                        "resident snapshot').  'auto' promotes only when "
                        "an accelerator backs the mesh (CPU hosts keep "
                        "host columns, logged once); 'on' forces "
                        "promotion (the CPU differential shape); 'off' "
                        "disables the lane.  The built-in "
                        "device_residency_evict degradation action "
                        "demotes resident groups on SLO breach")
    p.add_argument("--audit-expand", action="store_true",
                   help="expansion generator stage in the audit sweep: "
                        "generator objects (per ExpansionTemplate "
                        "applyTo) expand through the batched mutlane "
                        "stage and their resultants — implied Pods with "
                        "Source=Generated mutation applied — are audited "
                        "at sweep scale with the template's "
                        "enforcementAction override (README 'Batched "
                        "mutation & expansion')")
    p.add_argument("--fleet-config", default="",
                   help="fleet mode: JSON roster of clusters "
                        "({'clusters': [{'id': ..., 'manifests': "
                        "[...]}]}) — one process multiplexes every "
                        "cluster's audit plane behind SHARED per-library "
                        "runtimes (clusters running the same template "
                        "library share compiled executables; a second "
                        "same-library cluster boots with zero lowering) "
                        "and the fleet sweep packs small clusters' "
                        "same-group chunks into device-sized dispatches. "
                        "Honors --compile-cache (one shared cache), "
                        "--snapshot-spill (per-cluster subdirs), "
                        "--audit-interval/--audit-chunk-size/--once "
                        "(README 'Fleet mode')")
    p.add_argument("--mutate-ingest", default="dict",
                   choices=["dict", "raw", "differential"],
                   help="/v1/mutate burst columnizer: 'dict' keeps the "
                        "dict-walk lane byte-for-byte; 'raw' serializes "
                        "each burst once and feeds the PR 4 raw-bytes "
                        "threaded C columnizer (GIL released) — match "
                        "walks and patch emission still read the dict "
                        "objects, so outcomes are lane-invariant; "
                        "'differential' runs raw THEN dict per batch "
                        "and asserts the columns bit-identical")
    p.add_argument("--mutate-lane", default="batched",
                   choices=["batched", "host", "differential"],
                   help="/v1/mutate serving lane: 'batched' coalesces "
                        "mutate reviews into one columnar lane pass "
                        "(host fixed-point fallback for unsupported "
                        "mutators); 'host' is the per-object reference "
                        "path; 'differential' runs the batched lane AND "
                        "asserts it bit-identical to the reference per "
                        "batch (debugging)")
    p.add_argument("--pipeline", default="auto",
                   choices=["auto", "on", "off", "differential"],
                   help="audit sweep schedule: 'auto' runs the staged "
                        "host pipeline (list->flatten->dispatch->collect"
                        "->fold on separate threads, bounded queues) when "
                        "the host has >1 effective core and degrades to "
                        "the serial eager-poll schedule otherwise; "
                        "'on'/'off' force; 'differential' runs both and "
                        "asserts bit-identical output (debugging)")
    p.add_argument("--pipeline-flatten-workers", type=int, default=0,
                   help="threads in the pipeline's flatten stage; 0 = "
                        "auto (2 when the host has >=4 effective cores). "
                        "The C columnizer already shards each chunk over "
                        "an internal pthread pool; extra workers overlap "
                        "the Python assembly slices across chunks")
    p.add_argument("--flatten-workers", type=int, default=0,
                   help="multiprocess flatten worker pool for sweep "
                        "chunks: fan contiguous RawJSON byte spans of "
                        "each chunk across N worker PROCESSES (each runs "
                        "the C columnizer against a batch-local vocab; "
                        "results merge into the shared vocab on the "
                        "dispatch thread, bit-identical to in-process — "
                        "see ops/flatten.FlattenWorkerPool). 0 = the "
                        "exact in-process path (the 1-core default); "
                        "with --flatten-lane differential the worker "
                        "pool is additionally asserted column- and "
                        "vocab-identical per chunk")
    p.add_argument("--shard-chunks", type=int, default=0,
                   help="pack K consecutive same-group audit chunks "
                        "into one mesh-wide dispatch (object axis "
                        "sharded over the mesh 'data' axis) — K ~= "
                        "device count keeps each chip at "
                        "audit-chunk-size objects while per-dispatch "
                        "fixed costs amortize K-fold; 0/1 = off")
    p.add_argument("--flatten-lane", default="auto",
                   choices=["auto", "dict", "raw", "py", "differential"],
                   help="sweep columnizer lane: 'auto' feeds raw JSON "
                        "bytes from the lister straight through the "
                        "threaded C columnizer when available (falling "
                        "back to the GIL-bound dict walker, then "
                        "Python); 'raw'/'dict'/'py' force a lane; "
                        "'differential' runs raw THEN dict per chunk "
                        "and asserts bit-identical columns (debugging)")
    p.add_argument("--extdata-lane", default="batched",
                   choices=["batched", "perkey", "differential"],
                   help="external-data resolution lane: 'batched' dedupes "
                        "provider keys across each admission burst / audit "
                        "chunk, bulk-fetches per provider into resident "
                        "columns and joins verdicts on device; 'perkey' "
                        "keeps the per-key ProviderCache reference path "
                        "(external-data templates stay on the exact "
                        "interpreter); 'differential' runs batched AND "
                        "asserts verdicts + resolved values bit-identical "
                        "to per-key")
    p.add_argument("--extdata-max-keys", type=int, default=256,
                   help="max keys per bulk provider call (the batched "
                        "lane chunks larger deduped miss lists into "
                        "multiple transport sends)")
    p.add_argument("--extdata-fanout", type=int, default=4,
                   help="per-provider concurrency of the batched lane's "
                        "bulk fetches: a chunk referencing N providers "
                        "lands their miss lists across this many threads "
                        "(1 = strictly serial, the pre-fanout behavior)")
    p.add_argument("--generation-swap", default="on",
                   choices=["on", "off"],
                   help="template-churn compile lane: 'on' stages "
                        "post-boot template/constraint mutations, "
                        "compiles the next generation on a background "
                        "thread and atomically swaps executables in "
                        "(the serving path never pays lowering); 'off' "
                        "compiles inline on the reconcile path, "
                        "bit-identical to the pre-generation behavior. "
                        "Boot (manifests + warm) is always synchronous")
    p.add_argument("--compile-cache", default="",
                   help="directory for the on-disk compile cache: "
                        "lowered template programs keyed by (template "
                        "digest, engine, jax/jaxlib version, "
                        "flatten-schema version) with a vocab snapshot "
                        "replay — a warm restart or --once run skips "
                        "lowering entirely.  (JAX's persistent XLA cache "
                        "is placed by JAX_COMPILATION_CACHE_DIR, else "
                        "<checkout>/.jax_cache — never by this flag)")
    p.add_argument("--collect", default="reduced",
                   choices=["reduced", "masks", "differential"],
                   help="sweep collect lane: 'reduced' folds verdicts ON "
                        "DEVICE (per-constraint totals + top-k kept "
                        "selection + mask occupancy in one small packed "
                        "transfer — O(kept) device->host bytes, not "
                        "O(objects x constraints)); 'masks' ships the "
                        "bit grid and folds on the host (the reference "
                        "lane); 'differential' runs both per chunk and "
                        "asserts totals/kept/occupancy bit-identical")
    p.add_argument("--export-dir", default="",
                   help="enable disk export of audit violations")
    p.add_argument("--log-denies", action="store_true",
                   help="log structured deny events (reference --log-denies)")
    p.add_argument("--emit-admission-events", action="store_true",
                   help="emit K8s Events on admission violations "
                        "(reference --emit-admission-events)")
    p.add_argument("--admission-events-involved-namespace",
                   action="store_true",
                   help="emit admission Events in the violating object's "
                        "namespace instead of the gatekeeper namespace")
    p.add_argument("--emit-audit-events", action="store_true",
                   help="emit K8s Events on audit violations "
                        "(reference --emit-audit-events)")
    p.add_argument("--audit-events-involved-namespace",
                   action="store_true",
                   help="emit audit Events in the violating object's "
                        "namespace instead of the gatekeeper namespace")
    p.add_argument("--gatekeeper-namespace", default="gatekeeper-system",
                   help="namespace Events land in by default")
    p.add_argument("--log-stats-admission", action="store_true",
                   help="log per-request evaluation stats (reference "
                        "--log-stats-admission)")
    p.add_argument("--certs-dir", default="",
                   help="serve TLS using (or generating) certs in this dir")
    p.add_argument("--client-ca-file", default="",
                   help="require and verify client certificates against "
                        "this CA (reference --client-ca-name)")
    p.add_argument("--tls-min-version", default="1.3",
                   choices=["1.2", "1.3"])
    p.add_argument("--shutdown-delay", type=float, default=0.0,
                   help="seconds to keep serving after SIGTERM before "
                        "shutting down (reference --shutdown-delay); "
                        "readiness answers 503 {draining:true} for the "
                        "whole window so the LB deregisters first")
    p.add_argument("--drain-timeout", type=float, default=10.0,
                   help="graceful-drain budget in seconds: after SIGTERM "
                        "(and --shutdown-delay) the listener stops "
                        "accepting and in-flight admissions + the "
                        "batcher queue drain to completion within this "
                        "budget — zero accepted verdicts lost")
    p.add_argument("--webhook-backlog", type=int, default=128,
                   help="kernel listen(2) accept-queue depth for the "
                        "webhook socket (unanswered TCP connects. "
                        "Distinct from the overload limiter's cost-aware "
                        "admission queue, which holds ACCEPTED requests "
                        "waiting for a review slot — see README "
                        "'Overload & drain semantics')")
    p.add_argument("--overload-limiter", default="on",
                   choices=["on", "off"],
                   help="adaptive-concurrency admission gate in front of "
                        "the validating webhook (AIMD on review latency "
                        "vs a baseline EWMA + bounded cost-aware queue); "
                        "'on' is bit-identical to 'off' while unloaded "
                        "(differential-tested); sheds resolve per "
                        "--webhook-failure-policy")
    p.add_argument("--overload-max-inflight", type=int, default=64,
                   help="upper bound of the adaptive in-flight limit")
    p.add_argument("--overload-queue-depth", type=int, default=256,
                   help="max requests waiting in the admission queue "
                        "before sheds begin")
    p.add_argument("--overload-queue-cost", type=float, default=256e6,
                   help="max summed admission cost (object bytes x "
                        "matched-constraint estimate) queued before "
                        "sheds begin")
    p.add_argument("--qos", default="off", choices=["on", "off"],
                   help="per-tenant / per-priority admission QoS on the "
                        "overload path: priority lanes (system / "
                        "break-glass ahead of user traffic, shed last), "
                        "weighted-fair (deficit-round-robin) dequeue "
                        "across tenants, per-tenant inflight caps + "
                        "queue-cost budgets, and tenant-aware "
                        "displacement (the heaviest tenant sheds "
                        "first).  'off' (the compat default) keeps the "
                        "single cost-aware FIFO bit-identical to "
                        "previous releases (README 'Tenant QoS & "
                        "fairness')")
    p.add_argument("--qos-config", default="",
                   help="JSON file of QoS priority levels / tenant "
                        "weights / caps, mirroring the apiserver APF "
                        "PriorityLevel shape (see README 'Tenant QoS & "
                        "fairness'); empty = the built-in lane set "
                        "(kube-system + gatekeeper-system + system: "
                        "users ahead of break-glass ahead of everyone, "
                        "namespace as the tenant key)")
    p.add_argument("--qos-ledger-decay", default="events",
                   choices=["events", "slo-window"],
                   help="decay driver for the QoS displacement ledger "
                        "(who is 'heaviest'): 'events' (the default) "
                        "halves totals per fixed charge count — "
                        "deterministic replay; 'slo-window' halves them "
                        "per elapsed SLO short-window on the SLO "
                        "engine's clock, so tenant heaviness ages on "
                        "the same timebase the burn-rate windows use "
                        "(an idle gap forgets a past burst)")
    p.add_argument("--enable-profile", action="store_true",
                   help="serve /debug/profile?seconds=N (pprof equivalent)")
    p.add_argument("--fail-open-on-error", action="store_true",
                   help="admit (with a warning) when the review path raises "
                        "internally, instead of the reference's Errored "
                        "allowed=false code-500 response")
    p.add_argument("--exempt-namespace", action="append", default=[],
                   help="namespace allowed to set the ignore label "
                        "(repeatable; reference --exempt-namespace)")
    p.add_argument("--exempt-namespace-prefix", action="append", default=[],
                   help="namespace name prefix allowed to set the ignore "
                        "label (repeatable)")
    p.add_argument("--exempt-namespace-suffix", action="append", default=[],
                   help="namespace name suffix allowed to set the ignore "
                        "label (repeatable)")
    p.add_argument("--cert-rotation-check-s", type=float, default=3600.0,
                   help="cert expiry check interval for the rotation loop")
    p.add_argument("--management-manifests", default="",
                   help="remote-cluster mode: status/secret state routes to "
                        "a separate management cluster seeded from this "
                        "directory (reference --enable-remote-cluster)")
    p.add_argument("--coordinator", default="",
                   help="multi-host: coordinator address host:port "
                        "(joins a global JAX mesh across processes)")
    p.add_argument("--num-processes", type=int, default=1)
    p.add_argument("--process-id", type=int, default=0)
    p.add_argument("--once", action="store_true",
                   help="run one audit sweep and exit (no servers)")
    p.add_argument("--webhook-small-batch", type=int, default=None,
                   help="admission batches this size or smaller take the "
                        "per-review interpreter lane instead of the "
                        "device verdict grid (default 8 — the measured "
                        "grid-launch crossover; the lanes agree "
                        "bit-for-bit)")
    p.add_argument("--chaos", default="",
                   help="fault-injection spec (JSON file: {\"seed\": 0, "
                        "\"faults\": [{\"site\": ..., \"mode\": sleep|"
                        "hang|error|partial, ...}]}) installed process-"
                        "wide — the deterministic chaos harness for "
                        "exercising the resilience layer (README "
                        "'Failure semantics')")
    p.add_argument("--trace", default="",
                   help="write a Chrome trace-event JSON (Perfetto-"
                        "loadable) of the kept traces to this path on "
                        "exit; also serves the live tail-sampled ring "
                        "buffer at /debug/traces next to /metrics")
    p.add_argument("--trace-buffer", action="store_true",
                   help="enable the span tracer without a file export "
                        "(ring buffer served at /debug/traces only)")
    p.add_argument("--trace-slow-ms", type=float, default=0.0,
                   help="tail-sampling latency threshold: traces whose "
                        "root span is slower than this are ALWAYS kept; "
                        "the rest keep at --trace-sample (0 = no "
                        "threshold, keep per --trace-sample alone)")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="keep probability for traces under the "
                        "--trace-slow-ms threshold (1.0 keeps all; 0.0 "
                        "is the empty sampler — span machinery runs, "
                        "nothing retained)")
    p.add_argument("--trace-seed", type=int, default=None,
                   help="seed the trace/span ID generator and sampler "
                        "(deterministic IDs for differential runs; "
                        "default: OS entropy)")
    p.add_argument("--cost-attribution", default="on",
                   choices=["on", "off"],
                   help="per-template cost attribution: shared device "
                        "passes apportion wall time across the "
                        "constraint grid by row occupancy "
                        "(gatekeeper_constraint_eval_seconds, "
                        "/debug/cost, `gator bench --attribution`)")
    p.add_argument("--slo", default="on", choices=["on", "off"],
                   help="in-process SLO engine: declarative objectives "
                        "(admission/mutate P99, shed rate, audit "
                        "staleness) with multi-window burn rates — "
                        "gatekeeper_slo_* gauges, /debug/slo, breach "
                        "span events")
    p.add_argument("--slo-config", default="",
                   help="JSON file of SLO objectives (and optional burn "
                        "tiers) replacing the built-in defaults — see "
                        "README 'Observability' for the format")
    p.add_argument("--slo-interval", type=float, default=10.0,
                   help="seconds between SLO engine evaluations")
    p.add_argument("--slo-brownout", action="store_true",
                   help="feed SLO burn into the overload brownout "
                        "ladder: a burning latency objective browns out "
                        "optional work (stale lookups, audit device-"
                        "lane yield) BEFORE the admission queue backs "
                        "up (off keeps the ladder queue-driven only)")
    p.add_argument("--slo-degradation", default="off",
                   choices=["on", "off"],
                   help="targeted degradation maps: each objective's "
                        "ordered, revocable action list (ns_cache_stale "
                        "-> extdata_stale -> shed_harder; "
                        "audit_yield_release -> resync_defer) activates "
                        "step-by-step on burn breach and releases in "
                        "reverse on recovery — the surgical alternative "
                        "to the scalar --slo-brownout ladder (both can "
                        "run together)")
    p.add_argument("--flight-recorder", type=int, default=2048,
                   help="admission flight recorder: ring capacity of "
                        "structured admission/mutation/shed decision "
                        "records served at /debug/decisions?uid= "
                        "(0 disables)")
    p.add_argument("--flight-recorder-sink", default="",
                   help="append every flight-recorder decision to this "
                        "JSONL file (the operator's black box; decision "
                        "metadata only, never object bodies — unless "
                        "--flight-recorder-capture)")
    p.add_argument("--flight-recorder-sink-max-mb", type=float,
                   default=0.0,
                   help="rotate the sink when it reaches this many MB "
                        "(sink -> sink.1 -> sink.2 ...; 0 = unbounded). "
                        "gator decisions/triage read rotated sets "
                        "transparently")
    p.add_argument("--flight-recorder-sink-keep", type=int, default=3,
                   help="rotated sink files retained past the live one "
                        "(oldest dropped on rotation)")
    p.add_argument("--flight-recorder-capture", action="store_true",
                   help="capture mode: sink lines additionally carry "
                        "the raw admission request (the `gator replay` "
                        "corpus). The in-memory ring stays metadata-"
                        "only; the sink then holds Secrets-grade data")
    p.add_argument("--shadow-candidate", action="append", default=[],
                   help="shadow canary: candidate library file/dir "
                        "(repeatable). Copies of live admissions "
                        "evaluate against it off the response path — "
                        "verdicts are never answered; /debug/shadow, "
                        "gatekeeper_shadow_* metrics, and the shadow-"
                        "divergence-rate SLO objective carry the "
                        "promote/abort signal")
    p.add_argument("--shadow-sink", default="",
                   help="append shadow verdicts to this JSONL file "
                        "(the shadow flight-recorder stream)")
    p.add_argument("--webhook-deadline", type=float, default=0.0,
                   help="per-admission wall-clock budget in seconds; on "
                        "expiry the request resolves per "
                        "--webhook-failure-policy instead of stalling "
                        "the apiserver (0 disables)")
    p.add_argument("--webhook-failure-policy", default="fail",
                   choices=["ignore", "fail"],
                   help="what a failed/timed-out review answers: "
                        "'ignore' fails open (allow + warning "
                        "annotation), 'fail' fails closed (deny with "
                        "reason) — the reference webhook failurePolicy")
    p.add_argument("--webhook-workers", type=int, default=1,
                   help="serve the webhook from N processes sharing one "
                        "port via SO_REUSEPORT (the kernel load-balances "
                        "connections; each worker is a full replica of "
                        "the serving stack).  The multi-core answer to "
                        "the reference's goroutine-per-request model "
                        "(policy.go:116-120)")
    p.add_argument("--reuse-port", action="store_true",
                   help="bind the webhook port with SO_REUSEPORT (set "
                        "automatically for --webhook-workers children)")
    args = p.parse_args(argv)

    if args.fleet_config:
        # fleet mode is its own process shape (N clusters' audit planes
        # behind shared runtimes) — the single-cluster wiring below
        # does not apply
        from gatekeeper_tpu.fleet.run import run_fleet

        return run_fleet(args)

    worker_procs: list = []
    if args.webhook_workers > 1 and args.once:
        print("--webhook-workers ignored with --once (no servers run)",
              file=sys.stderr)
        args.webhook_workers = 1
    if args.webhook_workers > 1:
        # documented gate: on hosts with fewer effective cores than
        # workers, SO_REUSEPORT processes convoy on the CPU (a P99 of
        # seconds on one core).  Serve multi-worker only when each
        # worker can actually get a core.
        from gatekeeper_tpu.pipeline import effective_cpu_count

        cores = effective_cpu_count()
        if cores < args.webhook_workers:
            print(f"WARNING: --webhook-workers {args.webhook_workers} on "
                  f"a {cores}-core host: workers will convoy on the CPU "
                  f"(measured 36x P99 inflation on one core — see README "
                  f"'Failure semantics'); use at most {max(1, cores)} "
                  f"workers here", file=sys.stderr)
        if args.port == 0:
            p.error("--webhook-workers needs an explicit --port "
                    "(ephemeral ports cannot be shared)")
        if args.certs_dir:
            # generate serving certs BEFORE spawning workers: N processes
            # racing first-boot generation would overwrite each other's
            # key/cert pairs (mismatched tls.crt/tls.key)
            import os

            from gatekeeper_tpu.webhook.certs import generate_certs

            if not os.path.exists(os.path.join(args.certs_dir, "tls.crt")):
                generate_certs(args.certs_dir)
        import subprocess

        child_argv = list(argv) if argv is not None else sys.argv[1:]
        # strip the workers flag (children must not fork grandchildren)
        # and the parent's --operation set (children serve webhooks ONLY
        # — exactly one audit/controller process per --operation split,
        # as in the reference Deployment)
        stripped: list = []
        skip = False
        for a in child_argv:
            if skip:
                skip = False
                continue
            if a in ("--webhook-workers", "--operation", "--trace"):
                # --trace: N workers would race the export-file write at
                # exit; only the parent writes the artifact
                skip = True
                continue
            if a.startswith(("--webhook-workers=", "--operation=",
                             "--trace=")):
                continue
            stripped.append(a)
        child = [a for a in stripped if a != "--once"]
        child += ["--reuse-port", "--operation", "webhook",
                  "--operation", "mutation-webhook",
                  # only the parent runs cert rotation: N concurrent
                  # renewals would interleave generate_certs writes into
                  # mismatched tls.crt/tls.key pairs
                  "--cert-rotation-check-s", "0"]
        for i in range(args.webhook_workers - 1):
            worker_procs.append(subprocess.Popen(
                [sys.executable, "-m", "gatekeeper_tpu"] + child))
        args.reuse_port = True

    if args.coordinator:
        from gatekeeper_tpu.parallel.distributed import init_distributed

        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)
        print(f"joined global mesh: process {args.process_id}/"
              f"{args.num_processes}", file=sys.stderr)

    from gatekeeper_tpu.apis.constraints import WEBHOOK_EP
    from gatekeeper_tpu.audit.manager import AuditConfig, AuditManager
    from gatekeeper_tpu.client.client import Client
    from gatekeeper_tpu.controller.manager import ALL_OPERATIONS, Manager
    from gatekeeper_tpu.drivers.cel_driver import CELDriver
    from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
    from gatekeeper_tpu.export.system import ExportSystem
    from gatekeeper_tpu.gator import reader
    from gatekeeper_tpu.metrics.registry import MetricsRegistry
    from gatekeeper_tpu.sync.source import FakeCluster, FileSource
    from gatekeeper_tpu.target.target import K8sValidationTarget
    from gatekeeper_tpu.webhook.mutation import MutationHandler
    from gatekeeper_tpu.webhook.namespacelabel import NamespaceLabelHandler
    from gatekeeper_tpu.webhook.policy import Batcher, ValidationHandler
    from gatekeeper_tpu.webhook.server import WebhookServer

    operations = args.operation or list(ALL_OPERATIONS)
    metrics = MetricsRegistry()
    tracer = None
    if args.trace or args.trace_buffer:
        from gatekeeper_tpu.observability import tracing

        tracer = tracing.Tracer(
            seed=args.trace_seed,
            slow_threshold_s=(args.trace_slow_ms / 1000.0
                              if args.trace_slow_ms > 0 else None),
            sample_rate=args.trace_sample,
            metrics=metrics,
        )
        tracing.install(tracer)
        print("span tracer active"
              + (f" (export: {args.trace})" if args.trace else
                 " (ring buffer at /debug/traces)"), file=sys.stderr)
    if args.chaos:
        from gatekeeper_tpu.resilience import faults

        faults.set_metrics_registry(metrics)
        faults.install(faults.load_chaos_spec(args.chaos))
        print(f"chaos harness active: {args.chaos}", file=sys.stderr)
    # overload protection + graceful drain (resilience/overload.py):
    # the drain coordinator always exists (SIGTERM drives it); the
    # adaptive limiter gates the validating webhook when enabled —
    # installed process-wide so the brownout ladder reaches the
    # externaldata cache and the audit sweep's device-lane yield
    from gatekeeper_tpu.resilience import overload as _overload

    drain = _overload.DrainCoordinator(metrics=metrics)
    overload_ctl = None
    if args.overload_limiter == "on" and not args.once:
        from gatekeeper_tpu.resilience.qos import qos_from_args

        qos_cfg = qos_from_args(args.qos, args.qos_config)
        overload_ctl = _overload.OverloadController(
            _overload.OverloadConfig(
                max_inflight=args.overload_max_inflight,
                queue_depth=args.overload_queue_depth,
                queue_cost=args.overload_queue_cost,
                qos=qos_cfg,
            ),
            metrics=metrics)
        _overload.install(overload_ctl)
        if qos_cfg is not None:
            print(f"admission QoS active: "
                  f"{len(qos_cfg.levels)} priority lanes "
                  f"({', '.join(lv.name for lv in qos_cfg.levels)}), "
                  f"tenant key {qos_cfg.tenant_key}, "
                  f"inflight cap {qos_cfg.tenant_inflight_cap or 'none'} "
                  f"(/debug/overload)", file=sys.stderr)
    # the L6 observability trio (README "Observability"): cost
    # attribution + SLO engine + flight recorder, all metric-registry
    # backed and served from the /debug endpoints next to /metrics
    from gatekeeper_tpu.observability import costattr as _costattr
    from gatekeeper_tpu.observability import flightrec as _flightrec
    from gatekeeper_tpu.observability import slo as _slo

    cost_attr = None
    if args.cost_attribution == "on":
        cost_attr = _costattr.CostAttribution(metrics=metrics)
        _costattr.install(cost_attr)
        if overload_ctl is not None and args.qos == "on":
            # the {tenant} axis feeds QoS displacement: measured
            # per-tenant eval cost decides who is "heaviest", not
            # arrival order
            overload_ctl.set_tenant_cost_input(cost_attr.tenant_totals)
    flight_rec = None
    if args.flight_recorder > 0 and not args.once:
        flight_rec = _flightrec.FlightRecorder(
            capacity=args.flight_recorder,
            sink_path=args.flight_recorder_sink or None,
            metrics=metrics,
            capture=args.flight_recorder_capture,
            sink_max_bytes=int(args.flight_recorder_sink_max_mb
                               * 1024 * 1024),
            sink_keep=args.flight_recorder_sink_keep)
        _flightrec.install(flight_rec)
    slo_engine = None
    if args.slo == "on" and not args.once:
        degradations = None
        if args.slo_degradation == "on":
            # targeted per-objective degradation maps: the registry the
            # overload controller / ProviderCache / AuditManager consult
            # (degradation_active) and the engine drives edges into
            degradations = _overload.DegradationRegistry(metrics=metrics)
            _overload.install_degradations(degradations)
        slo_kw: dict = {"degradations": degradations}
        if args.slo_config:
            try:
                cfg = _slo.load_config(args.slo_config, degradations)
            except _slo.SLOConfigError as e:
                # fail fast at boot: a malformed objective silently
                # dropped is an SLO that never pages
                print(f"slo config: {e}", file=sys.stderr)
                return 2
            slo_kw["objectives"] = cfg["objectives"]
            if cfg["tiers"]:
                slo_kw["tiers"] = cfg["tiers"]
        elif args.shadow_candidate:
            # shadow canary on: the divergence-rate objective rides the
            # default set (an explicit --slo-config replaces defaults
            # wholesale, shadow objective included, like everything else)
            from gatekeeper_tpu.replay.shadow import SHADOW_OBJECTIVE

            slo_kw["objectives"] = (list(_slo.DEFAULT_OBJECTIVES)
                                    + [SHADOW_OBJECTIVE])
        slo_engine = _slo.SLOEngine(metrics, brownout=overload_ctl,
                                    **slo_kw)
        if args.slo_brownout and overload_ctl is not None:
            overload_ctl.set_slo_input(slo_engine.pressure)
        slo_engine.start(interval_s=args.slo_interval)
        print(f"SLO engine active: "
              f"{len(slo_engine.objectives)} objectives, tick every "
              f"{args.slo_interval:.0f}s (/debug/slo)"
              + (", degradation maps armed"
                 if degradations is not None else ""), file=sys.stderr)
    if args.qos == "on" and args.qos_ledger_decay == "slo-window" \
            and overload_ctl is not None:
        # displacement-ledger decay on the SLO window clock (default
        # 'events' keeps the deterministic event-count decay untouched)
        if slo_engine is not None:
            overload_ctl.set_qos_ledger_clock(
                slo_engine.window_clock, slo_engine.shortest_window_s())
        else:
            overload_ctl.set_qos_ledger_clock(time.monotonic, 300.0)
        print("qos ledger decay: slo-window", file=sys.stderr)
    cel = CELDriver()
    if args.evaluate_sidecar:
        from gatekeeper_tpu.drivers.remote import RemoteDriver

        tpu = RemoteDriver(args.evaluate_sidecar)
        # the sidecar container may still be initializing its devices:
        # wait for channel readiness instead of crash-looping on a race
        import grpc as _grpc

        try:
            _grpc.channel_ready_future(tpu._channel).result(timeout=120)
            print(f"evaluation plane: sidecar {args.evaluate_sidecar} "
                  f"({tpu.dump()['sidecar']})", file=sys.stderr)
        except Exception as e:
            print(f"evaluate sidecar unreachable after 120s: {e}",
                  file=sys.stderr)
            return 1
    else:
        from gatekeeper_tpu.utils.xla_cache import configure_xla_cache

        configure_xla_cache()
        compile_cache = None
        if args.compile_cache:
            from gatekeeper_tpu.drivers.generation import CompileCache

            compile_cache = CompileCache(args.compile_cache,
                                         metrics=metrics)
            print(f"compile cache: {args.compile_cache}", file=sys.stderr)
        tpu = TpuDriver(cel_driver=cel, metrics=metrics,
                        generation_swap=args.generation_swap == "on",
                        compile_cache=compile_cache)
    client = Client(target=K8sValidationTarget(),
                    drivers=[tpu, cel],
                    enforcement_points=[WEBHOOK_EP, "audit.gatekeeper.sh"])
    if getattr(tpu, "gen_coord", None) is not None:
        # pre-swap warm traces changed kernels at the real serving shape
        tpu.gen_coord.constraints_fn = client.constraints
    shadow_lane = None
    if args.shadow_candidate and not args.once:
        # continuous shadow canary (replay/shadow.py): the candidate
        # library loads through the same on-disk compile cache as
        # serving, so a warmed candidate attaches with zero fresh
        # lowerings; the webhook's per-decision hook feeds the lane
        from gatekeeper_tpu.gator import reader as _reader
        from gatekeeper_tpu.replay import core as _replay_core
        from gatekeeper_tpu.replay import shadow as _shadow

        try:
            _cand_docs = _reader.read_sources(args.shadow_candidate)
            _cand_rt = _replay_core.load_candidate(
                _cand_docs, compile_cache_dir=args.compile_cache,
                metrics=metrics)
            _shadow_rec = None
            if args.shadow_sink:
                _shadow_rec = _flightrec.FlightRecorder(
                    capacity=1024, sink_path=args.shadow_sink)
            shadow_lane = _shadow.ShadowLane(
                _cand_rt, serving_client=client,
                candidate_docs=_cand_docs, recorder=_shadow_rec,
                metrics=metrics).start()
            _shadow.install(shadow_lane)
            if slo_engine is not None:
                # divergence-rate breach -> automatic canary abort (the
                # objective only rides the engine when the shadow lane
                # is configured, so the hook always has its metric)
                shadow_lane.bind_slo(slo_engine)
            print(f"shadow canary active: {len(_cand_docs)} candidate "
                  f"docs (/debug/shadow"
                  + (", slo auto-abort armed" if slo_engine is not None
                     else "") + ")", file=sys.stderr)
        except Exception as e:
            print(f"shadow canary disabled: {e}", file=sys.stderr)
    kube_cluster = None
    if args.kubeconfig:
        from gatekeeper_tpu.sync.kube import KubeCluster, KubeConfig

        cfg = (KubeConfig.in_cluster() if args.kubeconfig == "in-cluster"
               else KubeConfig.from_kubeconfig(args.kubeconfig))
        kube_cluster = cluster = KubeCluster(cfg, metrics=metrics)
        print(f"informer plane: apiserver {cfg.server}", file=sys.stderr)
    else:
        cluster = FakeCluster()
    if args.management_manifests:
        # remote-cluster mode: gatekeeper-internal state (status group +
        # Secrets) lives on the management side; everything else — incl. a
        # live --kubeconfig apiserver — is the target
        from gatekeeper_tpu.sync.routing import RoutingCluster

        mgmt = FakeCluster()
        FileSource(args.management_manifests).populate(mgmt)
        cluster = RoutingCluster(mgmt, cluster)
        if kube_cluster is not None:
            kube_cluster = cluster  # audit discovery routes via the target
    export = ExportSystem()
    if args.export_dir:
        export.upsert_connection("disk", "disk", {"path": args.export_dir})
    # batched external-data join lane (extdata/lane.py): one process-wide
    # lane over the manager's provider cache — the webhook's device grid,
    # the audit sweep and mutation-placeholder resolution all dedupe
    # their keys through it; 'perkey' keeps the PR 2 per-key reference
    # behavior (external-data templates stay on the interpreter)
    from gatekeeper_tpu.externaldata.providers import ProviderCache
    from gatekeeper_tpu.extdata import lane as _extlane

    provider_cache = ProviderCache(metrics=metrics)
    extdata_lane = _extlane.ExtDataLane(
        provider_cache, mode=args.extdata_lane,
        max_keys_per_call=args.extdata_max_keys, metrics=metrics,
        fanout=args.extdata_fanout)
    _extlane.install(extdata_lane)
    if args.extdata_lane != "batched":
        print(f"extdata lane: {args.extdata_lane}", file=sys.stderr)
    mgr = Manager(client, cluster, operations=operations,
                  provider_cache=provider_cache,
                  extdata_lane=extdata_lane,
                  export_system=export, metrics=metrics,
                  readiness_retries=args.readiness_retries).start()

    if args.manifests:
        FileSource(*args.manifests).populate(cluster)
    mgr.tracker.all_populated()

    lowered = tpu.lowered_kinds()
    print(f"templates: {len(client.templates())} "
          f"({len(lowered)} on the TPU verdict path), "
          f"constraints: {len(client.constraints())}", file=sys.stderr)

    audit_mgr = None
    snapshot = None
    snap_ingester = None
    snap_spiller = None
    snap_residency = None
    spill_load = None
    warm_cache = None
    evaluator = None
    if mgr.is_assigned("audit") or args.once:
        if args.evaluate_sidecar:
            from gatekeeper_tpu.drivers.remote import RemoteEvaluator

            evaluator = RemoteEvaluator(
                tpu, violations_limit=args.constraint_violations_limit)
        else:
            # only the local path touches jax (the sidecar-mode control
            # plane stays accelerator-free)
            from gatekeeper_tpu.parallel.sharded import (
                ShardedEvaluator,
                make_mesh,
            )

            evaluator = ShardedEvaluator(
                tpu, make_mesh(),
                violations_limit=args.constraint_violations_limit,
                flatten_lane=args.flatten_lane,
                metrics=metrics,
                collect=args.collect,
                flatten_workers=args.flatten_workers)

        if kube_cluster is not None:
            # discovery-driven audit listing (auditResources,
            # pkg/audit/manager.go:369-422): every listable GVK, paged;
            # transient apiserver errors skip the sweep, never kill the pod
            def lister():
                try:
                    gvks = kube_cluster.server_preferred_gvks()
                except Exception as e:
                    print(f"audit discovery failed: {e}", file=sys.stderr)
                    return
                for gvk in gvks:
                    try:
                        yield from kube_cluster.list_iter(gvk)
                    except Exception as e:
                        print(f"audit list {gvk}: {e}", file=sys.stderr)
        else:
            def lister():
                return iter(cluster.list())
        audit_event_sink = None
        if args.emit_audit_events:
            from gatekeeper_tpu.sync import events as _events

            audit_event_sink = _events.audit_event_sink(
                _events.EventRecorder(
                    cluster, "gatekeeper-audit",
                    gk_namespace=args.gatekeeper_namespace,
                    involved_namespace=(
                        args.audit_events_involved_namespace),
                    on_error=lambda e: print(
                        f"audit event emit failed: {e}", file=sys.stderr)))
        audit_source = args.audit_source
        if audit_source == "snapshot":
            if args.evaluate_sidecar:
                # the snapshot lane slices resident columns into device
                # chunks locally (sweep_flatten_from_batch) — the
                # sidecar's RPC evaluator has no such seam
                print("--audit-source snapshot needs a local evaluator; "
                      "falling back to relist", file=sys.stderr)
                audit_source = "relist"
            else:
                from gatekeeper_tpu.snapshot import (ClusterSnapshot,
                                                     SnapshotConfig,
                                                     SnapshotSpill,
                                                     SnapshotSpiller,
                                                     WatchIngester,
                                                     gvks_of,
                                                     templates_digest)

                snapshot = ClusterSnapshot(evaluator, SnapshotConfig(),
                                           metrics=metrics)
                spill_load = None
                if args.snapshot_spill:
                    snap_spill = SnapshotSpill(
                        args.snapshot_spill, metrics=metrics,
                        compress=args.snapshot_spill_compress,
                        delta=args.snapshot_spill_delta,
                        full_every=args.snapshot_spill_full_every)
                    from gatekeeper_tpu.apis.constraints import AUDIT_EP \
                        as _AEP

                    audit_cons = [c for c in client.constraints()
                                  if c.actions_for(_AEP)]
                    spill_load = snap_spill.load(
                        snapshot, audit_cons,
                        extdata_lane=extdata_lane,
                        templates=templates_digest(client))
                    if spill_load is not None:
                        print(f"snapshot spill loaded: "
                              f"{spill_load['rows']} rows warm, zero "
                              f"relist (resubscribing from recorded rv)",
                              file=sys.stderr)
                    else:
                        print("snapshot spill miss "
                              f"({snap_spill.stats()['miss_reasons']}); "
                              "booting with a clean relist",
                              file=sys.stderr)
                watch_src = kube_cluster if kube_cluster is not None \
                    else cluster
                if kube_cluster is not None:
                    try:
                        watch_gvks = kube_cluster.server_preferred_gvks()
                    except Exception as e:
                        print(f"snapshot discovery failed: {e}",
                              file=sys.stderr)
                        watch_gvks = []
                else:
                    watch_gvks = gvks_of(cluster.list())
                snap_ingester = WatchIngester(
                    snapshot, watch_src, watch_gvks,
                    from_rvs=(spill_load or {}).get("rvs"),
                    on_error=lambda e: print(
                        f"snapshot watch subscribe failed: {e}",
                        file=sys.stderr)).start()
                if args.snapshot_spill:
                    snap_spiller = SnapshotSpiller(
                        snap_spill, snapshot,
                        rvs_fn=lambda: dict(snap_ingester.rvs),
                        extdata_lane=extdata_lane,
                        templates_fn=lambda: templates_digest(client))
                if args.snapshot_residency != "off":
                    from gatekeeper_tpu.snapshot import DeviceResidency

                    snap_residency = DeviceResidency(
                        evaluator, metrics=metrics,
                        mode=args.snapshot_residency)
                    _gc = getattr(tpu, "gen_coord", None)
                    if _gc is not None:
                        # generation swaps drop the device mirrors
                        # eagerly (new schemas/layouts)
                        _gc.attach_residency(snap_residency)
                print(f"resident snapshot active: watching "
                      f"{len(watch_gvks)} GVKs, resync every "
                      f"{args.snapshot_resync_every} intervals",
                      file=sys.stderr)
        audit_mgr = AuditManager(
            client,
            lister=lister,
            config=AuditConfig(
                interval_s=args.audit_interval,
                violations_limit=args.constraint_violations_limit,
                chunk_size=args.audit_chunk_size,
                pipeline=args.pipeline,
                pipeline_flatten_workers=args.pipeline_flatten_workers,
                shard_chunks=args.shard_chunks,
                audit_source=audit_source,
                resync_every=args.snapshot_resync_every,
                resync_rotate=args.snapshot_resync_rotate,
                expand_generated=args.audit_expand,
            ),
            evaluator=evaluator,
            export_system=export,  # Connection CRs register here too
            event_sink=audit_event_sink,
            log_violations=args.log_denies,
            metrics=metrics,
            snapshot=snapshot,
            expansion_system=mgr.expansion_system,
            spiller=snap_spiller,
            residency=snap_residency,
        )
        if snapshot is not None and snapshot.warm_loaded \
                and spill_load is not None:
            audit_mgr.restore_spill_aux(spill_load.get("aux") or {})
        if args.compile_cache and not args.evaluate_sidecar \
                and not args.once:
            # warm-state replay (drivers/generation.WarmStateCache):
            # re-land the fused sweep traces + the admission warm-ref
            # kernels recorded by the previous process, so the first
            # tick/burst after this restart retraces nothing — JAX's
            # persistent XLA cache answers the compiles
            from gatekeeper_tpu.drivers.generation import WarmStateCache

            warm_cache = WarmStateCache(args.compile_cache,
                                        metrics=metrics)
            rep = warm_cache.replay(tpu, evaluator)
            if rep["hit"]:
                print(f"warm state replayed: {rep['sweep_traces']} "
                      f"sweep traces landed", file=sys.stderr)

    def export_trace():
        if tracer is None or not args.trace:
            return
        from gatekeeper_tpu.observability import write_chrome_trace

        n = write_chrome_trace(args.trace, tracer)
        print(f"trace: {n} events ({tracer.kept} traces kept, "
              f"{tracer.sampled_out} sampled out) -> {args.trace} "
              f"(load in ui.perfetto.dev or chrome://tracing)",
              file=sys.stderr)

    if args.once:
        run = audit_mgr.audit()
        if snap_spiller is not None:
            # a --once sweep is a natural spill point: the NEXT --once
            # (or server boot) warm-starts off it, mirroring how the
            # compile cache serves one-shot runs
            snap_spiller.spill_now()
        total = sum(run.total_violations.values())
        print(f"audit: {run.total_objects} objects, {total} violations "
              f"in {run.duration_s:.2f}s "
              f"(flatten_workers={run.flatten_workers}, "
              f"n_devices={run.n_devices}, "
              f"shard_chunks={run.shard_chunks})"
              + (f" [INCOMPLETE: {run.failed_chunks} chunks dropped, "
                 f"{run.retried_chunks} retried]" if run.incomplete
                 else ""), file=sys.stderr)
        for key, kept in sorted(run.kept.items()):
            for v in kept:
                print(f"  {key[0]}/{key[1]}: {v.kind} "
                      f"{v.namespace + '/' if v.namespace else ''}{v.name}: "
                      f"{v.message}")
        export_trace()
        # a pass that dropped chunks has no verdicts for them: a one-shot
        # run must not report that as success (served mode keeps going
        # with the partial results and the incomplete marker)
        return 1 if run.incomplete else 0

    # namespace lookup for the webhook hot path: with a live apiserver,
    # serve from a watch-fed cache (the reference's cached client with
    # API-reader fallback, policy.go:694-702) — never a blocking GET per
    # admission request
    if kube_cluster is not None:
        ns_cache: dict = {}

        def _ns_event(ev):
            name = (ev.obj.get("metadata") or {}).get("name", "")
            if ev.type == "DELETED":
                ns_cache.pop(name, None)
            else:
                ns_cache[name] = ev.obj

        kube_cluster.subscribe(("", "v1", "Namespace"), _ns_event,
                               replay=True)

        def namespace_lookup(name):
            hit = ns_cache.get(name)
            if hit is not None:
                return hit
            return kube_cluster.get(("", "v1", "Namespace"), "", name)
    else:
        def namespace_lookup(name):
            return cluster.get(("", "v1", "Namespace"), "", name)

    batcher = Batcher(client, stats=args.log_stats_admission,
                      small_batch=args.webhook_small_batch,
                      metrics=metrics).start()
    mutation_batcher = None
    mutation_handler = None
    if mgr.is_assigned("mutation-webhook"):
        if args.mutate_lane == "host":
            mutation_handler = MutationHandler(
                mgr.mutation_system,
                namespace_lookup=namespace_lookup,
                process_excluder=mgr.excluder,
            )
        else:
            # the batched lane: mutate reviews coalesce into one
            # columnar pass, sharing the validation path's overload gate
            # and zero-loss drain (README 'Batched mutation & expansion')
            from gatekeeper_tpu.mutlane import (BatchedMutationHandler,
                                                MutationBatcher,
                                                MutationLane)

            mut_lane = MutationLane(
                mgr.mutation_system, metrics=metrics,
                differential=args.mutate_lane == "differential",
                ingest=args.mutate_ingest,
                # mutator churn recompiles on the generation thread too
                # (bursts keep the previous revision until the install)
                coordinator=getattr(tpu, "gen_coord", None))
            mutation_batcher = MutationBatcher(
                mut_lane, metrics=metrics).start()
            mutation_handler = BatchedMutationHandler(
                mgr.mutation_system,
                lane=mut_lane,
                namespace_lookup=namespace_lookup,
                process_excluder=mgr.excluder,
                batcher=mutation_batcher,
                metrics=metrics,
                overload=overload_ctl,
                failure_policy=("ignore" if args.fail_open_on_error
                                else args.webhook_failure_policy),
            )
    admission_sink = None
    if args.emit_admission_events:
        from gatekeeper_tpu.sync import events as _events

        admission_sink = _events.admission_event_sink(
            _events.EventRecorder(
                cluster, "gatekeeper-webhook",
                gk_namespace=args.gatekeeper_namespace,
                involved_namespace=args.admission_events_involved_namespace,
                on_error=lambda e: print(
                    f"admission event emit failed: {e}", file=sys.stderr)))
    server = None
    if mgr.is_assigned("webhook") or mgr.is_assigned("mutation-webhook"):
        # warm every grid-lane pad bucket before serving: readiness
        # already gates traffic (the reference's warm-cache contract,
        # readiness/setup.go:28-41) and a lazily-compiled batch shape
        # would otherwise stall the first saturated admission burst for
        # seconds
        if client.templates():
            from gatekeeper_tpu.match.match import SOURCE_ORIGINAL
            from gatekeeper_tpu.target.review import AugmentedUnstructured

            _pod = {"apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": "warmup", "namespace": "default"},
                    "spec": {"containers": [
                        {"name": "c", "image": "warmup"}]}}
            _warm = [AugmentedUnstructured(object=dict(_pod),
                                           source=SOURCE_ORIGINAL)
                     for _ in range(batcher.max_batch)]
            n = max(1, batcher.small_batch + 1)
            while n <= batcher.max_batch:
                client.review_batch(_warm[:n])
                n *= 2
            client.review_batch(_warm)
        certfile = keyfile = None
        if args.certs_dir:
            import os

            if kube_cluster is not None:
                # live cluster: the cert-controller-equivalent bootstrap —
                # chain lives in the cert Secret (one replica generates,
                # all consume), caBundle injected into the webhook configs
                from gatekeeper_tpu.webhook.certs import \
                    ensure_cluster_certs

                certfile, keyfile = ensure_cluster_certs(
                    kube_cluster, args.certs_dir)
                args.certs_dir = os.path.dirname(certfile)
            else:
                from gatekeeper_tpu.webhook.certs import generate_certs

                if not os.path.exists(
                        os.path.join(args.certs_dir, "tls.crt")):
                    generate_certs(args.certs_dir)
                certfile = os.path.join(args.certs_dir, "tls.crt")
                keyfile = os.path.join(args.certs_dir, "tls.key")
        server = WebhookServer(
            client_ca_file=args.client_ca_file or None,
            tls_min_version=args.tls_min_version,
            enable_profile=args.enable_profile,
            validation_handler=ValidationHandler(
                client,
                expansion_system=mgr.expansion_system,
                process_excluder=mgr.excluder,
                namespace_lookup=namespace_lookup,
                batcher=batcher,
                log_denies=args.log_denies,
                event_sink=admission_sink,
                metrics=metrics,
                fail_open=args.fail_open_on_error,
                failure_policy=("ignore" if args.fail_open_on_error
                                else args.webhook_failure_policy),
                deadline_budget_s=args.webhook_deadline,
                trace_config=lambda: mgr.validation_traces,
                log_stats=args.log_stats_admission,
                overload=overload_ctl,
                snapshot=snapshot,  # warm namespace/referential cache
            ) if mgr.is_assigned("webhook") else None,
            mutation_handler=mutation_handler,
            namespace_label_handler=NamespaceLabelHandler(
                exempt_namespaces=args.exempt_namespace,
                exempt_prefixes=args.exempt_namespace_prefix,
                exempt_suffixes=args.exempt_namespace_suffix,
            ),
            port=args.port,
            certfile=certfile,
            keyfile=keyfile,
            # drain pulls readiness BEFORE the listener closes (the LB
            # deregisters during --shutdown-delay)
            readiness_check=lambda: (not drain.draining
                                     and mgr.tracker.satisfied()),
            readiness_stats=mgr.tracker.stats,
            metrics=metrics,
            reuse_port=args.reuse_port,
            backlog=args.webhook_backlog,
            batcher=batcher,
            mutation_batcher=mutation_batcher,
            cost_attribution=cost_attr,
            slo_engine=slo_engine,
            flight_recorder=flight_rec,
        ).start()
        print(f"webhook serving on :{server.port}", file=sys.stderr)
        if args.certs_dir and args.cert_rotation_check_s > 0:
            # check-s <= 0 disables rotation (SO_REUSEPORT worker
            # children: only the parent rotates, or N processes would
            # race renewal-time generation into mismatched pairs)
            import threading

            from gatekeeper_tpu.webhook.certs import rotation_loop

            rot_stop = threading.Event()
            threading.Thread(
                target=rotation_loop,
                args=(args.certs_dir, server, rot_stop,
                      args.cert_rotation_check_s),
                kwargs={"cluster": kube_cluster},
                daemon=True,
            ).start()

    # boot reconcile + warm are done: flip template churn to the
    # background generation lane (README "Generations & compile cache")
    # — from here on a ConstraintTemplate add/edit stages + enqueues,
    # the compile thread builds/warms the next generation, and the swap
    # lands off the serving path
    if mgr.begin_background_compile():
        print("generation swap active: post-boot template churn "
              "compiles in the background", file=sys.stderr)

    # graceful shutdown (the drain state machine, README "Overload &
    # drain semantics"): on SIGTERM readiness flips 503 {draining:true}
    # immediately (the LB deregisters during --shutdown-delay while the
    # listener KEEPS serving), then the listener stops accepting and
    # in-flight handlers + the batcher queue drain to completion within
    # --drain-timeout, the tracer/metrics flush, and worker children
    # drain in sequence — zero accepted verdicts lost
    import signal
    import threading

    stopping = threading.Event()

    def _on_term(signum, frame):
        if not drain.begin(f"signal {signum}"):
            return  # a second SIGTERM while already draining
        print(f"signal {signum}: draining"
              + (f" (serving {args.shutdown_delay:.0f}s more for LB "
                 f"deregistration)" if args.shutdown_delay else ""),
              file=sys.stderr)
        if server is not None:
            server.begin_drain()  # healthz 503 + retire keep-alives
        for wp in worker_procs:  # children start their own drains now
            wp.terminate()
        if args.shutdown_delay:
            time.sleep(args.shutdown_delay)
        stopping.set()
        if audit_mgr is not None:
            audit_mgr.stop()

    signal.signal(signal.SIGTERM, _on_term)

    try:
        if audit_mgr is not None:
            audit_mgr.run_forever()
        else:
            while not stopping.wait(1.0):
                pass
    except KeyboardInterrupt:
        pass
    finally:
        drain.begin("shutdown")
        if server:
            # stops accepting, then drains in-flight handlers AND the
            # batcher queue inside the budget before closing
            drained = server.stop(drain_timeout=args.drain_timeout)
            if not drained:
                print(f"WARNING: drain exceeded --drain-timeout "
                      f"{args.drain_timeout:.0f}s; in-flight work "
                      f"abandoned", file=sys.stderr)
        batcher.stop()  # idempotent (server.stop drained it already)
        if mutation_batcher is not None:
            mutation_batcher.stop()
        if snap_spiller is not None:
            # final spill (idempotent with run_forever's exit flush): a
            # clean drain never loses the resident state it paid for
            snap_spiller.stop(flush=True)
        if snap_ingester is not None:
            snap_ingester.stop()
        if warm_cache is not None:
            # persist the warm execution state beside the compile cache
            # so the NEXT process replays traces instead of retracing
            warm_cache.save(tpu, evaluator)
        _gc = getattr(tpu, "gen_coord", None)
        if _gc is not None:
            _gc.stop()
        if shadow_lane is not None:
            from gatekeeper_tpu.replay import shadow as _shadow

            _shadow.uninstall()
            shadow_lane.stop()
            if shadow_lane.recorder is not None:
                shadow_lane.recorder.close()
        if slo_engine is not None:
            slo_engine.stop()
        if flight_rec is not None:
            flight_rec.close()  # flush the JSONL black box
        export_trace()  # tracer flush happens after the last span closed
        # worker children drain in sequence: each runs this same
        # machinery; the parent waits for them one at a time so every
        # replica finishes its in-flight verdicts before the port dies
        for wp in worker_procs:
            wp.terminate()
        for wp in worker_procs:
            try:
                wp.wait(timeout=max(5.0, args.drain_timeout))
            except Exception:
                wp.kill()
        dt = drain.finish()
        if drain.drain_seconds is not None and server is not None:
            print(f"drain complete in {dt:.2f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
