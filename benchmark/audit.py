"""Traffic of kind ``audit``: back-to-back full audit passes over the
configuration's cluster, a closed loop of one.

Set-up: the corpus (a JSONL spill, one shard per process), the reference
children, the program, the inventory, ``warm_pass``, an audit of the
reference sample, one set-up pass, the device's verdicts on the sample.
Window: whole ``AuditManager.audit()`` passes, as many as start and end
inside it.  ``audit_pass_s`` is their mean: the passes fall into two modes,
with one or with two full garbage collections of the interpreter inside
them, and a median jumps from one mode to the other between runs.

``correct`` rests on the interpreter twice.  The sample goes through
``AuditManager.audit()`` itself, on the executables of the measured passes,
and its totals and kept violations must be the interpreter's; and the
(constraint, object) pairs a ``return_bits`` sweep finds on it must be the
interpreter's.  Every measured pass is then held to the set-up pass.  A
total is what the configuration's ``audit.exact_totals`` says it is: the
number of a constraint's results (true, ``AuditConfig``'s default and what
``python -m gatekeeper_tpu`` runs) or of its violating objects (false).
Every number compared goes into the result line, each beside its limit.

A mix with a ``churn`` block (``churn.py``) changes the cluster between
every two passes.  A pass is then held to what its own cluster owes: the
set-up pass's totals moved by every change of an object's count since.  A
``return_bits`` sweep of every changed object finds which versions violate
once the window has closed (so that the measured passes are the first to
meet each new name), held to the interpreter on a seeded sample of the
changed objects; a version counts 1 for each constraint it violates where a
total is the number of violating objects.  Where it is the number of
results, the children review every changed version, violating or not: a
version counts the interpreter's results for the constraint, and the
sweep's pairs are held to the interpreter's on every version, so that no
total rests on what the program found.  The kept violations of three passes
are held to the interpreter's review of the very bytes those passes listed.
A mix without the block runs as it always has.
"""

from __future__ import annotations

import hashlib
import json
import os
import resource
import sys
import time

from benchmark import churn, cluster, reference, stats, wiring
from benchmark.harness import Run


def corpus_key(spec: list, seed: int, per_kind: dict) -> str:
    with open(cluster.__file__, "rb") as f:
        generator = f.read()
    h = hashlib.sha256(generator)
    h.update(json.dumps([spec, seed, per_kind], sort_keys=True).encode())
    return h.hexdigest()


def make_corpus(run: Run, referential: list) -> tuple:
    """Every shard of the corpus, each written by a process of its own.  A
    corpus already there for the same generator, configuration, seed and
    size is read back.  Returns (shard paths, counts by kind)."""
    cfg = run.cell.config
    spec = cfg["cluster"]
    n_shards = -(-int(cfg["objects"]) // cluster.SHARD)
    per_kind = {k: -(-v // n_shards)
                for k, v in cfg["reference_sample"].items()}
    paths = [os.path.join(run.work, f"corpus.{s}.jsonl")
             for s in range(n_shards)]
    key = corpus_key([spec, cfg["objects"]], run.seed, per_kind)
    key_path = os.path.join(run.work, "corpus.key")
    made = False
    if os.path.exists(key_path):
        with open(key_path) as f:
            made = f.read() == key
    if not made:
        if os.path.exists(key_path):
            os.unlink(key_path)
        procs = []
        for s, path in enumerate(paths):
            job = path + ".job"
            with open(job, "w") as f:
                json.dump({"spec": spec, "objects": cfg["objects"],
                           "seed": run.seed, "shard": s, "path": path,
                           "referential": referential,
                           "per_kind": per_kind}, f)
            procs.append(run.spawn([cluster.__file__, job]))
        for p in procs:
            if p.wait() != 0:
                raise RuntimeError("a corpus shard failed")
        with open(key_path, "w") as f:
            f.write(key)
    counts: dict = {}
    for path in paths:
        with open(path + ".counts") as f:
            for kind, n in json.load(f).items():
                counts[kind] = counts.get(kind, 0) + n
    return paths, counts


def lister_of(paths: list):
    """The corpus as the audit lists it: RawJSON lines off the spill."""
    from gatekeeper_tpu.utils.rawjson import RawJSON

    def lister():
        for path in paths:
            with open(path, "rb") as f:
                for line in f:
                    yield RawJSON(line.rstrip(b"\n"))

    return lister


def read_sample(paths: list, sample_path: str) -> list:
    """[(corpus index, raw bytes)] of the stratified reference sample, also
    written as one file for the reference children."""
    rows = []
    with open(sample_path, "wb") as out:
        for path in paths:
            with open(path + ".sample", "rb") as f:
                for line in f:
                    out.write(line)
                    idx, _, raw = line.rstrip(b"\n").partition(b"\t")
                    rows.append((int(idx), raw))
    return rows


def audit_router(program):
    """Object kind -> its kind group, as the audit routes chunks."""
    from gatekeeper_tpu.apis.constraints import AUDIT_EP
    from gatekeeper_tpu.parallel.sharded import make_kind_router

    return make_kind_router([c for c in program.client.constraints()
                             if c.actions_for(AUDIT_EP)])


def group_sizes(router, counts: dict) -> dict:
    """Objects per kind group: the audit chunks by group."""
    sizes: dict = {}
    for kind, n in counts.items():
        g = router(kind)
        if g:
            sizes[g] = sizes.get(g, 0) + n
    return sizes


def by_group(router, sample: list) -> dict:
    """{kind group: [(corpus index, raw)]} of the sample, in its order."""
    from gatekeeper_tpu.utils.rawjson import RawJSON, peek_kind

    groups: dict = {}
    for idx, raw in sample:
        g = router(peek_kind(RawJSON(raw)))
        if g:
            groups.setdefault(g, []).append((idx, raw))
    return groups


def write_sample_corpus(groups: dict, sizes: dict, chunk: int,
                        path: str) -> list:
    """The reference sample as a corpus of its own, for the audit to sweep
    on the executables of the measured passes: each kind group's sample,
    over and over, to as many rows as that group's first chunk of the real
    corpus has.  Rows pad to a power of two and whether a chunk has pad
    rows decides its wire layout, both part of a sweep program's key: a
    sample swept at its own size would compile other programs than the
    ones measured.  Returns the corpus index of every listed object, in
    listing order."""
    order = []
    with open(path, "wb") as f:
        for g, members in groups.items():
            for j in range(min(sizes[g], chunk)):
                idx, raw = members[j % len(members)]
                f.write(raw + b"\n")
                order.append(idx)
    return order


def kept_agrees(got: list, want: list, limit: int) -> bool:
    """``got``: a constraint's kept violations, in kept order, each as
    (object, message).  ``want``: (object, the interpreter's messages) of
    every object that violates it, in listing order.  The audit keeps each
    violating object's results, in listing order, until ``limit`` are
    kept; the order of one object's results is not part of the answer, so
    the object the limit cuts through may keep any of its own."""
    i = 0
    for obj, msgs in want:
        if i == limit:
            break
        take = got[i:i + len(msgs)]
        i += len(take)
        owed = sorted((obj, m) for m in msgs)
        if len(take) == len(msgs):
            if sorted(take) != owed:
                return False
            continue
        if i != limit:
            return False
        for entry in take:
            if entry not in owed:
                return False
            owed.remove(entry)
    return i == len(got)


def sample_audit_problems(got, order: list, results: dict, ident: dict,
                          limit: int, exact: bool = False,
                          counts: dict | None = None,
                          implied: dict | None = None) -> list:
    """What ``got``, the audit of the sample corpus, reports otherwise than
    the interpreter.  ``results``: {corpus index: {constraint key:
    [messages]}}; ``ident``: {corpus index: (kind, namespace, name)}.
    ``exact`` is the configuration's ``audit.exact_totals``: a constraint's
    total is then the number of its results over the listed objects, as
    upstream's ``totalViolations`` counts, and otherwise the number of
    listed objects that violate it.  ``counts``, if given, takes how many
    constraints each comparison failed (the run's ``compared``).

    ``implied`` (``reference.implied_by_index``), where the configuration
    expands generator objects: what each generator line implies, resultant
    by resultant.  Its results count in the totals as a listed object's do,
    and are kept after every listed object's, in the order the generators
    were listed (the program audits the resultants once the pass has
    drained); a kept implied violation is held to its message and to its
    enforcement action, which the template may override."""
    problems = []
    short = got.incomplete or got.total_objects != len(order)
    if short:
        problems.append(f"sample audit: incomplete={got.incomplete}, "
                        f"{got.total_objects} of {len(order)} objects")
    totals: dict = {}
    violators: dict = {}
    for idx in order:
        for key, msgs in results.get(idx, {}).items():
            totals[key] = totals.get(key, 0) + (len(msgs) if exact else 1)
            violators.setdefault(key, []).append((ident[idx], msgs))
    resultants = set()
    for idx in order:
        for rid, per in (implied or {}).get(idx, ()):
            resultants.add(rid)
            for key, msgs in per.items():
                totals[key] = totals.get(key, 0) + (len(msgs) if exact
                                                    else 1)
                violators.setdefault(key, []).append((rid, msgs))
    totals_differ = kept_differ = 0
    for key, total in got.total_violations.items():
        if total != totals.get(key, 0):
            totals_differ += 1
            problems.append(f"sample audit: {key} totals {total}, the "
                            f"interpreter {totals.get(key, 0)}")
        kept = [((v.kind, v.namespace, v.name),
                 (v.message, v.enforcement_action)
                 if (v.kind, v.namespace, v.name) in resultants
                 else v.message)
                for v in got.kept[key]]
        if not kept_agrees(kept, violators.get(key, []), limit):
            kept_differ += 1
            problems.append(f"sample audit: {key} keeps other violations "
                            f"than the interpreter's first {limit}")
    missing = set(totals) - set(got.total_violations)
    if missing:
        problems.append(f"sample audit: no totals for {sorted(missing)}")
    if counts is not None:
        counts.update(sample_audit_short=int(short),
                      sample_totals_differ=totals_differ,
                      sample_kept_differ=kept_differ,
                      sample_totals_missing=len(missing))
    return problems


def device_pairs(program, groups: dict, chunk: int | None = None) -> set:
    """{(constraint key, corpus index)} the device sweep finds violated on
    the sample: each kind group swept with ``return_bits``, as an audit
    with exact totals sweeps it.  With ``chunk``, a group larger than that
    is swept in slices of ``chunk`` rows, the last filled up with the
    group's first members so that it runs on the program of the others."""
    from gatekeeper_tpu.apis.constraints import AUDIT_EP
    from gatekeeper_tpu.parallel.sharded import violation_rows
    from gatekeeper_tpu.utils.rawjson import RawJSON

    constraints = [c for c in program.client.constraints()
                   if c.actions_for(AUDIT_EP)]
    pairs: set = set()
    for g, members in groups.items():
        cons_g = [c for c in constraints if c.kind in g]
        step = chunk or len(members)
        for lo in range(0, len(members), step):
            part = members[lo:lo + step]
            if lo:
                part = part + members[:step - len(part)]
            rows = [RawJSON(raw) for _, raw in part]
            swept = program.evaluator.sweep(cons_g, rows, return_bits=True)
            missing = {c.kind for c in cons_g} - set(swept)
            if missing:
                raise RuntimeError(f"not evaluated on the device: {missing}")
            for kcons, _idx, _valid, _counts, hits in swept.values():
                for ci, con in enumerate(kcons):
                    for oi in violation_rows(hits, ci, len(rows)):
                        pairs.add((tuple(con.key()), part[int(oi)][0]))
    return pairs


def generator_kinds(program) -> set:
    """The kinds some ExpansionTemplate of the program applies to (every
    template here applies to kinds of one group and version): their objects
    are what a pass expands where the configuration says so."""
    if program.expansion is None:
        return set()
    return {k for t in program.expansion.templates() for entry in t.apply_to
            for k in entry.get("kinds") or ()}


def trace_plan(setup_pass_s: float, window_s: float, trace_from: int,
               trace_passes: int) -> tuple:
    """(index of the first traced pass of the window, traced passes).  The
    mix's own choice, unless the set-up pass says that the window holds
    fewer whole passes than that takes: then the window's first pass alone,
    so that a cell whose pass fills most of the window still has a traced
    run."""
    if setup_pass_s * (trace_from + trace_passes) > window_s:
        return 0, 1
    return trace_from, trace_passes


def hit_buffers(ev) -> dict:
    """What of the evaluator's adaptive state is part of a sweep program's
    key: each swept shape's hit-buffer size under ``return_bits``, or None
    where the shape is pinned to the bit grid, which has no buffer."""
    return {shape: None if st["pinned"] else st["cap"]
            for shape, st in ev.warm_state()["hit_state"].items()}


def settled_pass(mgr, ev, most: int = 3) -> tuple:
    """(the set-up pass, its seconds, passes run).  A chunk whose hits
    overflow its buffer under ``return_bits`` is swept again through the
    bit grid and the buffer grows, and it is the pass after that asks for
    the program of the new size: that pass is set-up's too, so that nothing
    compiles inside the window.  A shape the overflow pins to the bit grid
    needs none (the overflow itself ran that program), nor does a lane that
    sizes no buffer (the top-k lane's ladder is warmed whole)."""
    for n in range(1, most + 1):
        sized = hit_buffers(ev)
        t = time.monotonic()
        first = mgr.audit()
        seconds = time.monotonic() - t
        if all(now is None or now == sized[shape]
               for shape, now in hit_buffers(ev).items() if shape in sized):
            break
    return first, seconds, n


def canonical_run(audit_run) -> tuple:
    """Totals and kept violations of a pass, in a form two passes over one
    corpus must share."""
    kept = {key: sorted((v.message, v.kind, v.namespace, v.name)
                        for v in vs) for key, vs in audit_run.kept.items()}
    return dict(audit_run.total_violations), kept


def run(run: Run) -> dict:
    cfg, traffic = run.cell.config, run.cell.traffic
    referential = cfg["referential_kinds"]
    # first of all, so that a host without the chip is refused at once
    run.require_device()
    run.mark("native_jax_devices")
    paths, counts = make_corpus(run, referential)
    run.mark("corpus")
    sample_path = os.path.join(run.work, "sample.tsv")
    sample = read_sample(paths, sample_path)
    epochs = None
    if traffic.get("churn"):
        # the first epochs now, so that the interpreter reviews its sample
        # of the changed objects beside set-up; the rest once set-up's
        # passes have said how many the window can hold
        epochs = churn.Epochs(run, cfg, traffic["churn"], paths)
        epochs.make(churn.epochs_least(traffic["churn"]))
        epochs.wait()
        with open(sample_path, "ab") as f:
            for vid, raw in epochs.sample_first():
                f.write(b"%d\t" % vid + raw + b"\n")
        run.mark("churn_first_epochs")
    if traffic.get("churn") and cfg["audit"].get("expand_generated"):
        raise ValueError(f"{run.cell.name}: the churn ledger owes no "
                         "implied results; a mix with a churn block runs "
                         "no configuration that expands generators")
    inventory = [p + ".inv" for p in paths]
    ref = reference.Children(run.spawn, cfg, "audit", run.seed, inventory,
                             sample_path, run.work,
                             traffic["reference_children"])
    run.mark("sample_reference_children")
    program = wiring.Program(cfg, run.traced, run.seed, run.cell.chips)
    run.mark("program_library")
    try:
        return _measure(run, program, paths, counts, sample, ref, inventory,
                        epochs)
    finally:
        program.close()


def settle(run: Run, mgr, epochs) -> list:
    """Set-up's churned passes, [(epoch, the pass, executables it asked
    for, its seconds)]: an epoch is brought in and the cluster audited until
    a pass asks XLA for nothing, at most ``settle_passes`` times.  The first
    drift a boot meets is set-up's; what recurs is the window's."""
    out = []
    for _ in range(int(epochs.block["settle_passes"])):
        epochs.bring_in()
        t = time.monotonic()
        audited = mgr.audit()
        seconds = time.monotonic() - t
        asked = run.compiles_between(t, t + seconds)
        out.append((len(epochs.rows) - 1, audited, asked, seconds))
        if not asked:
            break
    return out


def churn_problems(run: Run, program, epochs, paths: list, inventory: list,
                   router, first, settled: list, runs: list,
                   pass_epoch: list, reviewed: dict, compared: dict,
                   told: dict) -> list:
    """``correct`` through the changes, once the window has closed; the
    numbers go into ``compared``, what the check itself cost into ``told``
    (the run's notes), what they found is returned as text.  ``reviewed``:
    {version number: {constraint key: [messages]}} of the interpreter's
    sample of the first epochs' changed objects, which it reviewed beside
    set-up; its sample of the later epochs', the objects three passes kept
    violations of and, where a total is the number of results, every listed
    version go to children started here."""
    cfg = run.cell.config
    n, limit = epochs.n, cfg["audit"]["violations_limit"]
    exact = cfg["audit"]["exact_totals"]
    rows = epochs.rows
    ledger = churn.Ledger(n, rows, churn.read_positions(
        paths, {pos for changes in rows for pos, _op, _prev, _raw in changes}))
    later = dict(epochs.sample_later())
    sampled = {**dict(epochs.sample_first()), **later}
    # every changed object, as the corpus had it and as each epoch made it,
    # on the device alone: nothing of the memo or of a pass is in this sweep
    t = time.monotonic()
    pairs = device_pairs(program, by_group(router, list(
        {**ledger.raw_of, **sampled}.items())), cfg["audit"]["chunk_size"])
    told["sweep_executables"] = run.compiles_between(t, time.monotonic())
    run.mark("churn_sweep")
    problems = []
    compared.update(touch_moved_a_verdict=0, kept_short=0, kept_stale=0,
                    kept_unfounded=0)
    # what the interpreter reviews here: the later epochs' sample, what the
    # first, the middle and the last pass kept and, where a total is the
    # number of results, every listed version that set-up's children did
    # not review, whatever the device found of it: its results are its
    # counts
    counted = set(ledger.raw_of) if exact else set()
    k = churn.RENDERED_PASSES
    at = sorted({(len(runs) - 1) * i // max(1, k - 1) for i in range(k)}
                if runs else ())
    unchanged = churn.locate(paths, {
        name for i in at for name in churn.kept_names(runs[i])
        if name not in ledger.position})
    kept_of = []
    by_raw: dict = {}  # the bytes listed -> their line of the review's input
    for raw in later.values():
        by_raw.setdefault(raw, len(by_raw))
    for i in at:
        listed, unlisted = ledger.kept_versions(pass_epoch[i], runs[i],
                                                unchanged)
        compared["kept_stale"] += len(unlisted)
        if unlisted:
            problems.append(f"pass {i} keeps violations of objects no one "
                            f"listed: {sorted(unlisted)[:3]}")
        kept_of.append((i, listed))
        for raw in listed.values():
            by_raw.setdefault(raw, len(by_raw))
    for vid in counted - set(reviewed):
        by_raw.setdefault(ledger.raw_of[vid], len(by_raw))
    path = os.path.join(run.work, "kept.tsv")
    with open(path, "wb") as f:
        for raw, rid in by_raw.items():
            f.write(b"%d\t" % rid + raw + b"\n")
    results: dict = {}
    parts = churn.children_for(len(by_raw), churn.REVIEWED_A_CHILD)
    cpu = resource.getrusage(resource.RUSAGE_CHILDREN)
    t = time.monotonic()
    if by_raw:
        results = reference.by_index(reference.Children(
            run.spawn, cfg, "audit", run.seed, inventory, path, run.work,
            parts, tag="kept").join())
    done = resource.getrusage(resource.RUSAGE_CHILDREN)
    told.update(counted_versions=len(counted), review_lines=len(by_raw),
                review_children=parts if by_raw else 0,
                review_s=time.monotonic() - t,
                review_child_cpu_s=(done.ru_utime + done.ru_stime
                                    - cpu.ru_utime - cpu.ru_stime))
    run.mark("churn_kept_review")
    by_bytes = {raw: results[rid] for raw, rid in by_raw.items()
                if rid in results}
    reviewed = dict(reviewed)
    reviewed.update((vid, by_bytes[raw]) for vid, raw in later.items()
                    if raw in by_bytes)
    # what each version counts: its results under exact totals, 1 for each
    # constraint the device finds it violating otherwise
    if exact:
        answers = {vid: reviewed.get(vid, by_bytes.get(ledger.raw_of[vid]))
                   for vid in counted}
        missing = sorted(vid for vid, per in answers.items() if per is None)
        answered = {vid: per for vid, per in answers.items()
                    if per is not None}
        counts = churn.result_counts(answered)
    else:
        missing, answered, counts = [], {}, churn.clipped(pairs)
    ledger.owe(counts, first.total_violations)
    compared["touch_moved_a_verdict"] = ledger.touch_moved
    if ledger.touch_moved:
        problems.append(f"{ledger.touch_moved} rewrites of resourceVersion "
                        "changed a count")
    passes = [(e, r, f"settle pass {i}")
              for i, (e, r, _asked, _s) in enumerate(settled)]
    passes += [(e, r, f"pass {i}")
               for i, (e, r) in enumerate(zip(pass_epoch, runs))]
    for e, r, name in passes:
        if r.incomplete or r.total_objects != n or e < 0:
            continue  # counted as passes_short or epochs_ran_out
        found = ledger.pass_problems(e, r, limit)
        compared["passes_differ"] += int(found["totals"] > 0)
        for key in ("kept_short", "kept_stale", "kept_unfounded"):
            compared[key] += found[key]
        if any(found.values()):
            problems.append(f"{name}, epoch {e}: {found}")
    # the sweep against the interpreter on every version it reviewed: the
    # seeded sample and, under exact totals, every listed version, so that
    # a violation the device misses is a disagreement and not a count of 0
    held = {**answered, **reviewed}
    interp = {(key, vid) for vid, per in held.items() for key in per}
    device = {(key, vid) for key, vid in pairs
              if vid in sampled or vid in answered}
    compared["churn_pairs_differ"] = len(device ^ interp)
    if device != interp:
        problems.append(
            f"device sweep != interpreter on {len(set(sampled) | set(held))} "
            f"changed versions: {len(device - interp)} device-only, "
            f"{len(interp - device)} interpreter-only, e.g. "
            f"{sorted(device ^ interp)[:3]}")
    # every one of the first epochs and the last one brought in
    owed = set(range(churn.SAMPLE_FIRST_EPOCHS)) | {len(rows) - 1}
    epochs_sampled = {vid // n - 1 for vid in sampled if vid in reviewed}
    compared["churn_sample_short"] = int(
        not owed <= epochs_sampled or set(sampled) != set(reviewed))
    if compared["churn_sample_short"]:
        problems.append(f"the interpreter reviewed {len(reviewed)} of "
                        f"{len(sampled)} sampled changed objects, of the "
                        f"epochs {sorted(epochs_sampled)}; owed "
                        f"{sorted(owed)}")
    compared["kept_messages_differ"] = 0
    for i, listed in kept_of:
        bad = churn.kept_message_problems(runs[i], listed, by_bytes)
        compared["kept_messages_differ"] += bad
        if bad:
            problems.append(f"pass {i}: {bad} constraints keep violations "
                            "the interpreter does not report of the bytes "
                            "listed")
    compared["churn_counts_missing"] = len(missing)
    if missing:
        problems.append(f"the interpreter gave no count for {len(missing)} "
                        f"listed versions, e.g. {missing[:3]}")
    return problems


def _measure(run, program, paths, counts, sample, ref, inventory,
             epochs=None) -> dict:
    cfg, traffic = run.cell.config, run.cell.traffic
    n_objects = int(cfg["objects"])
    chunk = cfg["audit"]["chunk_size"]
    n_inv = 0
    for path in inventory:
        with open(path, "rb") as f:
            n_inv += program.sync_inventory(json.loads(ln) for ln in f)
    run.mark("inventory")
    limit = cfg["audit"]["violations_limit"]
    router = audit_router(program)
    sizes = group_sizes(router, counts)
    groups = by_group(router, sample)
    sample_corpus = os.path.join(run.work, "sample.corpus.jsonl")
    order = write_sample_corpus(groups, sizes, chunk, sample_corpus)
    lister = (lister_of(paths) if epochs is None
              else churn.lister_of(paths, epochs.overlay))
    mgr = program.build_audit(lister)
    ev = program.evaluator
    # the lane the passes will sweep in: return_bits under exact totals
    ev.warm_pass(program.client.constraints(), lister(), chunk,
                 return_bits=cfg["audit"]["exact_totals"])
    run.mark("warm_pass")
    t_sample = time.monotonic()
    sampled = program.build_audit(lister_of([sample_corpus])).audit()
    run.mark("sample_audit")
    compiles_in_sample_audit = run.compiles_between(t_sample,
                                                    time.monotonic())
    first, setup_pass_s, setup_passes = settled_pass(mgr, ev)
    run.mark("setup_pass")
    settled: list = []
    if epochs is not None:
        settled = settle(run, mgr, epochs)
        # the children make the rest beside the sample's sweep, as many as
        # the window can hold at the pace of the fastest pass so far: a
        # pass that compiled (the set-up pass of a checkout's first run)
        # says nothing of the window's
        epochs.make(churn.epochs_wanted(epochs.block, run.seconds, min(
            [setup_pass_s] + [s for _e, _r, _asked, s in settled])))
        run.mark("churn_settle")
    program.begin_background_compile()
    want = canonical_run(first)
    device = device_pairs(program, groups)
    run.mark("sample_sweep")
    # corpus index -> {constraint key: [messages]}; the changed objects'
    # version numbers lie above every corpus index
    results, reviewed = {}, {}
    parts = ref.join()
    implied = (reference.implied_by_index(parts)
               if cfg["audit"].get("expand_generated") else None)
    for idx, per in reference.by_index(parts).items():
        if idx >= n_objects:
            reviewed[idx] = per
        elif per:
            results[idx] = per
    interp = {(key, idx) for idx, per in results.items() for key in per}
    run.mark("reference_join")
    if epochs is not None:
        epochs.wait()
        run.mark("churn_epochs")
    ident = {}
    for idx, raw in sample:
        meta = json.loads(raw)
        ident[idx] = (meta["kind"], meta["metadata"].get("namespace", ""),
                      meta["metadata"]["name"])
    # every number that decides ``correct``; each has the limit 0
    compared: dict = {}
    problems = sample_audit_problems(sampled, order, results, ident, limit,
                                     cfg["audit"]["exact_totals"], compared,
                                     implied)
    implied_results = 0
    if implied is not None:
        implied_results = sum(len(msgs) for entries in implied.values()
                              for _rid, per in entries
                              for msgs in per.values())
        compared["reference_implied_empty"] = int(not implied_results)
        if not implied_results:
            problems.append("the reference sample's generators imply no "
                            "violation")
    compared["device_pairs_differ"] = len(device ^ interp)
    compared["reference_sample_empty"] = int(not interp)
    compared["setup_pass_short"] = int(
        first.incomplete or first.total_objects != n_objects)
    if device != interp:
        problems.append(
            f"device sweep != interpreter on {len(sample)} objects: "
            f"{len(device - interp)} device-only, {len(interp - device)} "
            f"interpreter-only, e.g. {sorted(device ^ interp)[:3]}")
    if not interp:
        problems.append("the reference sample holds no violation")
    if first.incomplete or first.total_objects != n_objects:
        problems.append(f"set-up pass: incomplete={first.incomplete}, "
                        f"{first.total_objects} of {n_objects} objects")

    # --- the window ---------------------------------------------------------
    passes: list = []      # wall seconds of each whole pass
    began: list = []       # time.monotonic() at each one's start
    pass_epoch: list = []  # the newest epoch in each one's cluster
    ran_out = 0            # passes that found no epoch left to bring in
    runs: list = []
    fallbacks_in_setup = int(ev.perf.get("collect_fallbacks", 0))
    ev.perf_reset()
    mgr.perf = {}
    trace_from, trace_passes = trace_plan(
        setup_pass_s, run.seconds, traffic["trace_from_pass"],
        traffic["trace_passes"])
    if not run.traced:
        trace_passes = 0
    traced_passes = 0
    if run.traced:
        run.watch_gc()
    vocabulary_w0 = len(program.tpu.vocab)
    brought_in = len(epochs.rows) if epochs is not None else 0
    w0 = time.monotonic()
    w0_wall = time.time()
    setup_s = w0 - run.t0

    def one_pass() -> None:
        nonlocal ran_out
        if epochs is not None:
            # between two passes, outside either's clock
            ran_out += int(not epochs.bring_in())
            pass_epoch.append(len(epochs.rows) - 1)
        t = time.monotonic()
        runs.append(mgr.audit())
        passes.append(time.monotonic() - t)
        began.append(t)

    def room() -> bool:
        # a pass counts only if it ends inside the window, so none starts
        # that the passes so far say would not
        spent = time.monotonic() - w0
        longest = max(passes) if passes else 0.0
        return spent + longest <= run.seconds

    while room():
        if trace_passes and len(passes) == trace_from:
            with run.device_trace():
                for _ in range(trace_passes):
                    one_pass()
            traced_passes, trace_passes = trace_passes, 0
        else:
            one_pass()
    w1 = time.monotonic()
    w1_wall = time.time()
    vocabulary_w1 = len(program.tpu.vocab)
    # the window's own: what a churn mix sweeps after it adds to ev.perf
    perf = {"evaluator": dict(ev.perf), "manager": dict(mgr.perf)}
    if epochs is not None:
        # the sweep of the changed objects below is no part of the cell
        run.memory_peak = run.memory_peak_bytes()
        run.mark("window")

    attempted = failed = 0
    chunks_per_pass = sum(-(-n // chunk) for n in sizes.values())
    compared.update(passes_short=0, passes_differ=0,
                    window_empty=int(not passes))
    for i, r in enumerate(runs):
        attempted += chunks_per_pass
        failed += r.failed_chunks + r.retried_chunks
        if r.incomplete or r.total_objects != n_objects:
            compared["passes_short"] += 1
            problems.append(f"pass {i}: incomplete={r.incomplete}, "
                            f"{r.total_objects} objects")
        elif epochs is None and canonical_run(r) != want:
            compared["passes_differ"] += 1
            problems.append(f"pass {i}: totals or kept violations differ "
                            "from the set-up pass")
    fallbacks = int(perf["evaluator"].get("collect_fallbacks", 0))
    failed += fallbacks
    if not passes:
        problems.append("no whole pass fits the window")
    changed_in_window = 0
    told: dict = {}  # what the check after the window cost
    if epochs is not None:
        changed_in_window = sum(epochs.changed[brought_in:])
        compared["epochs_ran_out"] = ran_out
        if ran_out:
            problems.append(f"{ran_out} passes found none of the "
                            f"{epochs.made} epochs left")
        problems += churn_problems(run, program, epochs, paths, inventory,
                                   router, first, settled, runs, pass_epoch,
                                   reviewed, compared, told)
    for p in problems:
        print(f"benchmark: {p}", file=sys.stderr)

    compiles = run.compiles_between(w0, w1)
    spans = program.spans(w0_wall, w1_wall) if run.traced else []
    reduced = None
    if traced_passes:
        reduced = dict(run.reduce_trace(spans), passes=traced_passes)
    elif run.traced:
        raise RuntimeError(f"the window held {len(passes)} passes: too few "
                           "to trace")
    obs = {
        "perf": perf,
        "passes": len(passes), "objects": n_objects,
        "constraints": len(first.kept),
        "spans": spans, "hist": {},
        "counts": {"compiles_in_window": compiles,
                   "changed_share": (changed_in_window
                                     / (len(passes) * n_objects)
                                     if passes else None),
                   "new_strings_per_pass": (
                       (vocabulary_w1 - vocabulary_w0) / len(passes)
                       if passes else None)},
        "full_gc_s": run.full_gc_s_between(w0, w1) if run.traced else None,
        "loadgen": None, "trace": reduced,
    }
    generators = generator_kinds(program)
    notes = {
        "passes": len(passes), "pass_s": passes,
        "traced_from": trace_from, "traced_passes": traced_passes,
        "setup_passes": setup_passes,
        "pass_s_median": stats.median(passes) if passes else None,
        "objects": n_objects, "constraints": len(first.kept),
        "inventory_synced": n_inv, "reference_sample": len(sample),
        "reference_violating_pairs": len(interp),
        "sample_audit_objects": len(order),
        "sample_audit_violations": sum(sampled.total_violations.values()),
        "sample_audit_violating_objects": sum(
            len(results.get(idx, ())) for idx in order),
        "sample_audit_kept": sum(len(v) for v in sampled.kept.values()),
        "generators": sum(n for kind, n in counts.items()
                          if kind in generators),
        "sample_audit_generators": sum(ident[idx][0] in generators
                                       for idx in order),
        "reference_implied_results": implied_results,
        "compiles_in_sample_audit": compiles_in_sample_audit,
        "violations": sum(first.total_violations.values()),
        "kept": sum(len(v) for v in first.kept.values()),
        "schedule": "pipelined" if mgr.perf.get("pipelined") else "serial",
        "collect_fallbacks": fallbacks,
        "collect_fallbacks_in_setup": fallbacks_in_setup,
        "compiles_in_window": compiles,
        "compiles_in_setup": run.compiles_between(run.t0, w0),
        "xla_cache_dir": program.xla_cache_dir,
        "problems": problems,
    }
    if epochs is not None:
        notes["churn"] = {
            "epochs_made": epochs.made, "epochs_brought_in": len(epochs.rows),
            "changed": epochs.changed,
            "settle": [{"epoch": e, "executables_asked_for": asked,
                        "seconds": seconds}
                       for e, _r, asked, seconds in settled],
            "executables_by_pass": [
                run.compiles_between(t, t + s)
                for t, s in zip(began, passes)],
            "vocabulary": [vocabulary_w0, vocabulary_w1], **told}
    e2e = {"setup_s": setup_s}
    if passes:
        e2e["audit_pass_s"] = stats.mean(passes)
    return run.result(not problems, attempted, failed, e2e, obs, notes,
                      {name: {"value": n, "limit": 0}
                       for name, n in compared.items()})

