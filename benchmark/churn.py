"""A cluster that changes between two audit passes: the ``churn`` block of a
traffic mix (``traffic/audit-churn.json``), as epochs of changed objects.

Epoch ``e`` is what one audit interval changes: ``share_per_pass`` of the
cluster's objects, ``replaced`` of them Pods deleted and created anew as a
rollout does it (the same corpus position, the Pod as it stood, under a name
no object had before; ``new_image_share`` of them with their first
container on an image reference no object had before, and nothing else of
the Pod changed) and the rest touched, objects of any kind by the cluster's
kind mix whose ``metadata.resourceVersion`` is rewritten and nothing else.
Only Pods are ever replaced, so the kind at every position, the kind
counts, the group sizes, the chunk counts and the synced inventory's
content stand.

The plan (which positions, which serial numbers) is a pure function of the
configuration, the seed and the block; an epoch's bytes are a pure function
of the plan and the corpus, so any range of epochs can be made alone, in any
process.  Standard library only: the children that make epochs stay
JAX-free.

The block holds what defines the traffic (``share_per_pass``, ``replaced``,
``new_image_share``, ``settle_passes``).  What ``correct`` samples and how
many processes the host lends are constants here, the same for every mix.

A version of an object has a number: the corpus position for the object as
the corpus has it, ``(e + 1) * objects + position`` for what epoch ``e``
made of it.
"""

from __future__ import annotations

import collections
import hashlib
import json
import math
import os
import random
import sys

if __name__ == "__main__":  # a child: python benchmark/churn.py <job.json>
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import cluster  # noqa: E402

REPLACED, TOUCHED = "replaced", "touched"

# epochs made: as many as this many windows of passes would bring in at the
# pace of the fastest pass set-up ran
WINDOW_FACTOR = 2
# changed objects the interpreter reviews: SAMPLE_FIRST of the first
# SAMPLE_FIRST_EPOCHS epochs, beside set-up, and SAMPLE_LATER spread evenly
# over every later epoch the run brought in, once the window has closed
SAMPLE_FIRST_EPOCHS = 8
SAMPLE_FIRST = 512
SAMPLE_LATER = 512
# passes whose kept violations the interpreter renders: the first, the
# middle and the last of the window
RENDERED_PASSES = 3
# work one child is started for: changed objects made, objects reviewed
MADE_A_CHILD = 4096
REVIEWED_A_CHILD = 64

# epoch: the epoch that makes this version; pos: the corpus position; op;
# serial: the new Pod's name number, or the resourceVersion written;
# new_image: a replaced Pod's first container runs an image no object ran;
# before: the Change that made the version this one follows, None where it
# follows the corpus's own object
Change = collections.namedtuple(
    "Change", "epoch pos op serial new_image before")


def children_for(work: int, a_child: int) -> int:
    """Processes for ``work`` items at ``a_child`` items each: never more
    than the host has cores beside the run's own."""
    return max(1, min((os.cpu_count() or 2) - 1, -(-work // a_child)))


def version_id(n: int, epoch: int, pos: int) -> int:
    return (epoch + 1) * n + pos


def kinds_by_position(spec: dict, n: int) -> list:
    """The kind at every corpus position: the generator's own kind stream,
    which no seed touches (``cluster.Cluster.objects``)."""
    cl = cluster.Cluster(spec, n, 0)
    kinds = []
    for shard in range(cl.shards()):
        rng = random.Random(f"kinds:{shard}")
        lo = shard * cluster.SHARD
        kinds.extend(cl._pick(rng, cl._kinds, cl._kind_cum)
                     for _ in range(lo, min(n, lo + cluster.SHARD)))
    return kinds


class Plan:
    """Which objects each epoch changes, and how."""

    def __init__(self, spec: dict, n: int, seed: int, block: dict):
        self.n, self.seed = int(n), seed
        self.by_kind: dict = {}
        for pos, kind in enumerate(kinds_by_position(spec, self.n)):
            self.by_kind.setdefault(kind, []).append(pos)
        changed = round(float(block["share_per_pass"]) * self.n)
        self.n_replaced = round(changed * float(block["replaced"]))
        self.n_touched = changed - self.n_replaced
        self.n_new_image = round(self.n_replaced
                                 * float(block["new_image_share"]))
        if self.n_replaced > len(self.by_kind.get("Pod", ())):
            raise ValueError("churn: more Pods replaced a pass than the "
                             "cluster holds")
        kinds = [k for k in spec["kinds"] if self.by_kind.get(k)]
        self._kinds = kinds
        self._kind_cum = []
        total = 0.0
        for k in kinds:
            total += spec["kinds"][k]
            self._kind_cum.append(total)

    def epochs(self):
        """Epoch 0, 1, 2, ... without end, each a list of Change: the
        replaced Pods first, then the touched objects.  No position changes
        twice in one epoch."""
        newest: dict = {}  # pos -> the Change that made its newest version
        pods = self.by_kind.get("Pod", [])
        e = 0
        while True:
            rng = random.Random(f"{self.seed}:churn:{e}")
            changes = []
            taken = set()
            for j, pos in enumerate(rng.sample(pods, self.n_replaced)):
                taken.add(pos)
                changes.append(Change(
                    e, pos, REPLACED, self.n + e * self.n_replaced + j,
                    j < self.n_new_image, newest.get(pos)))
            for j in range(self.n_touched):
                while True:
                    kind = cluster.Cluster._pick(rng, self._kinds,
                                                 self._kind_cum)
                    at = self.by_kind[kind]
                    pos = at[rng.randrange(len(at))]
                    if pos not in taken:
                        break
                taken.add(pos)
                changes.append(Change(
                    e, pos, TOUCHED, 1 + e * self.n_touched + j, False,
                    newest.get(pos)))
            for c in changes:
                newest[c.pos] = c
            yield changes
            e += 1


def new_image(image: str, serial: int) -> str:
    """The same repository under a tag or a digest no object had."""
    if "@sha256:" in image:
        repo = image.partition("@")[0]
        return f"{repo}@sha256:" + hashlib.sha256(
            f"churn:{serial}".encode()).hexdigest()
    return f"{image.rpartition(':')[0]}:r{serial}"


def materialize(change: Change, base: bytes) -> bytes:
    """The bytes of the version ``change`` makes; ``base`` is the corpus's
    own line at that position.  A replaced Pod is the Pod that stood there,
    created anew: the generator's name under a number no object had, no
    ``resourceVersion`` of a write in place, and where the rollout is of a
    new build, its first container's image."""
    obj = json.loads(base if change.before is None
                     else materialize(change.before, base))
    meta = obj["metadata"]
    if change.op == TOUCHED:
        meta["resourceVersion"] = str(change.serial)
        return cluster.dumps(obj)
    meta["name"] = f"pod-{change.serial}"
    meta.pop("resourceVersion", None)
    if change.new_image:
        first = obj["spec"]["containers"][0]
        first["image"] = new_image(first["image"], change.serial)
    return cluster.dumps(obj)


def read_positions(paths: list, wanted) -> dict:
    """{corpus position: its line, without the newline} of ``wanted``."""
    out = {}
    if not wanted:
        return out
    i = 0
    for path in paths:
        with open(path, "rb") as f:
            for line in f:
                if i in wanted:
                    out[i] = line.rstrip(b"\n")
                i += 1
    return out


def epoch_path(work: str, e: int) -> str:
    return os.path.join(work, f"epoch.{e}.tsv")


def write_epochs(spec: dict, objects: int, seed: int, block: dict,
                 paths: list, work: str, lo: int, hi: int) -> None:
    """Epochs ``lo`` to ``hi - 1``, one file each: a line a change,
    ``position, op, previous epoch, bytes``, tab-separated."""
    plan = Plan(spec, objects, seed, block)
    mine = []
    for e, changes in enumerate(plan.epochs()):
        if e >= hi:
            break
        if e >= lo:
            mine.append(changes)
    base = read_positions(paths, {c.pos for changes in mine for c in changes})
    for changes in mine:
        tmp = epoch_path(work, changes[0].epoch) + ".tmp"
        with open(tmp, "wb") as f:
            for c in changes:
                prev = -1 if c.before is None else c.before.epoch
                f.write(b"%d\t%s\t%d\t" % (c.pos, c.op.encode(), prev)
                        + materialize(c, base[c.pos]) + b"\n")
        os.replace(tmp, epoch_path(work, changes[0].epoch))


def read_epoch(work: str, e: int) -> list:
    """[(position, op, previous epoch, bytes)] of epoch ``e``."""
    rows = []
    with open(epoch_path(work, e), "rb") as f:
        for line in f:
            pos, op, prev, raw = line.rstrip(b"\n").split(b"\t", 3)
            rows.append((int(pos), op.decode(), int(prev), raw))
    return rows


def epochs_least(block: dict) -> int:
    """The epochs set-up itself uses: the settle passes' and the first
    sample's."""
    return int(block["settle_passes"]) + SAMPLE_FIRST_EPOCHS


def epochs_wanted(block: dict, window_s: float, pass_s: float) -> int:
    """As many epochs as ``WINDOW_FACTOR`` windows of passes of ``pass_s``
    seconds would bring in, never fewer than set-up itself uses."""
    return max(epochs_least(block),
               math.ceil(WINDOW_FACTOR * window_s / max(pass_s, 1e-3)))


def sample_of(work: str, n: int, seed: int, epochs, total: int) -> list:
    """[(version number, bytes)]: ``total`` changed objects drawn from the
    seed, spread evenly over ``epochs`` (every one represented while there
    are no more epochs than objects; the last one always)."""
    out = []
    epochs = list(epochs)
    for i, e in enumerate(epochs):
        k = total * (i + 1) // len(epochs) - total * i // len(epochs)
        rows = read_epoch(work, e)
        rng = random.Random(f"{seed}:churn-sample:{e}")
        for pos, _op, _prev, raw in sorted(rng.sample(rows,
                                                      min(k, len(rows)))):
            out.append((version_id(n, e, pos), raw))
    return out


class Epochs:
    """The epochs of one run: made by JAX-free children, brought into the
    lister's overlay one at a time between passes."""

    def __init__(self, run, cfg: dict, block: dict, paths: list):
        self.run, self.cfg, self.block, self.paths = run, cfg, block, paths
        self.n = int(cfg["objects"])
        self.made = 0          # epochs asked of the children so far
        self.procs: list = []
        self.rows: list = []   # rows[e]: read_epoch(e), once brought in
        self.overlay: dict = {}  # position -> the bytes now listed there
        self.changed: list = []  # objects whose listed bytes epoch e changed

    def make(self, upto: int) -> None:
        """Start children for the epochs up to ``upto`` not yet made."""
        lo, hi = self.made, max(self.made, upto)
        if hi == lo:
            return
        parts = min(hi - lo, children_for(
            (hi - lo) * round(float(self.block["share_per_pass"]) * self.n),
            MADE_A_CHILD))
        step = -(-(hi - lo) // parts)
        for at in range(lo, hi, step):
            job = os.path.join(self.run.work, f"churn.{at}.job.json")
            with open(job, "w") as f:
                json.dump({"spec": self.cfg["cluster"], "objects": self.n,
                           "seed": self.run.seed, "block": self.block,
                           "paths": self.paths, "work": self.run.work,
                           "lo": at, "hi": min(hi, at + step)}, f)
            self.procs.append(self.run.spawn([os.path.abspath(__file__),
                                              job]))
        self.made = hi

    def wait(self) -> None:
        for p in self.procs:
            if p.wait() != 0:
                raise RuntimeError("a churn child failed")
        self.procs.clear()

    def bring_in(self) -> bool:
        """The next epoch into the overlay; False where none is left.
        Counts the objects whose listed bytes it changes (a position's
        first change differs from the corpus's line by construction: a new
        name, or a resourceVersion the generator never writes)."""
        e = len(self.rows)
        if e >= self.made:
            return False
        rows = read_epoch(self.run.work, e)
        self.rows.append(rows)
        changed = 0
        for pos, _op, _prev, raw in rows:
            if self.overlay.get(pos) != raw:
                changed += 1
            self.overlay[pos] = raw
        self.changed.append(changed)
        return True

    def sample_first(self) -> list:
        """What the interpreter reviews beside set-up: ``SAMPLE_FIRST``
        changed objects of the first ``SAMPLE_FIRST_EPOCHS`` epochs, each
        represented."""
        return sample_of(self.run.work, self.n, self.run.seed,
                         range(SAMPLE_FIRST_EPOCHS), SAMPLE_FIRST)

    def sample_later(self) -> list:
        """What it reviews once the window has closed: ``SAMPLE_LATER``
        changed objects of every later epoch brought in, the last among
        them; nothing where the run brought in no later epoch."""
        later = range(SAMPLE_FIRST_EPOCHS, len(self.rows))
        return (sample_of(self.run.work, self.n, self.run.seed, later,
                          SAMPLE_LATER) if later else [])


def lister_of(paths: list, overlay: dict):
    """``audit.lister_of`` with one lookup an object: a position the
    overlay holds is listed under the overlay's bytes."""
    from gatekeeper_tpu.utils.rawjson import RawJSON

    def lister():
        now = overlay.get
        i = 0
        for path in paths:
            with open(path, "rb") as f:
                for line in f:
                    raw = now(i)
                    yield RawJSON(line.rstrip(b"\n") if raw is None else raw)
                    i += 1

    return lister


# --- what a pass over epoch e's cluster must report ---------------------------

def identity(raw: bytes) -> tuple:
    """(kind, namespace, name), as a kept violation names its object."""
    obj = json.loads(raw)
    meta = obj["metadata"]
    return obj["kind"], meta.get("namespace", ""), meta["name"]


def locate(paths: list, names) -> dict:
    """{(kind, namespace, name): (corpus position, line)} of the corpus's
    own objects of those names; a name the corpus does not hold is left
    out.  The generator writes an object's position into its name, so that
    line is read first; what it does not settle is looked for in every
    line."""
    found: dict = {}
    guess: dict = {}
    for name in names:
        digits = name[2].rpartition("-")[2].lstrip("x")
        if digits.isdigit():
            guess.setdefault(int(digits), []).append(name)
    for pos, raw in read_positions(paths, set(guess)).items():
        if identity(raw) in guess[pos]:
            found[identity(raw)] = (pos, raw)
    rest = {b'"name":"%s"' % name[2].encode(): name
            for name in names if name not in found}
    if rest:
        i = 0
        for path in paths:
            with open(path, "rb") as f:
                for line in f:
                    for pat in rest:
                        if pat in line:
                            raw = line.rstrip(b"\n")
                            if identity(raw) == rest[pat]:
                                found[rest[pat]] = (i, raw)
                    i += 1
    return found


class Ledger:
    """Every version the passes listed, and what each pass owes.

    ``rows``: the epochs brought in, in order (``read_epoch``).  ``base``:
    {position: the corpus's own line} of every position they change.
    ``pairs``: the violating (constraint key, version number) pairs of all
    those versions, the corpus's own and the epochs', as the device found
    them.  ``setup_totals``: the set-up pass's totals, over the corpus as
    it is."""

    def __init__(self, n: int, rows: list, base: dict, pairs, setup_totals):
        self.n = n
        self.violated: dict = {}   # version number -> {constraint key}
        for key, vid in pairs:
            self.violated.setdefault(vid, set()).add(key)
        self.raw_of = dict(base)   # version number -> bytes
        self.history: dict = {}    # position -> [epochs that changed it]
        self.position: dict = {}   # name -> position, of changed objects
        # a replaced Pod's name -> the epoch it is gone from; a new Pod's
        # name -> the epoch it is listed from
        self.gone: dict = {}
        self.born: dict = {}
        self.totals: list = []     # totals[e]: owed over epoch e's cluster
        self.touch_moved = 0       # touches that changed a verdict
        totals = dict(setup_totals)
        for pos, raw in base.items():
            self.position[identity(raw)] = pos
        for e, changes in enumerate(rows):
            for pos, op, prev, raw in changes:
                old = pos if prev < 0 else version_id(n, prev, pos)
                new = version_id(n, e, pos)
                was = self.violated.get(old, set())
                now = self.violated.get(new, set())
                for key in was - now:
                    totals[key] = totals.get(key, 0) - 1
                for key in now - was:
                    totals[key] = totals.get(key, 0) + 1
                if op == TOUCHED:
                    self.touch_moved += int(was != now)
                else:
                    self.gone[identity(self.raw_of[old])] = e
                    self.born[identity(raw)] = e
                    self.position[identity(raw)] = pos
                self.raw_of[new] = raw
                self.history.setdefault(pos, []).append(e)
            self.totals.append(dict(totals))

    def version(self, e: int, pos: int) -> int:
        """The version number listed at ``pos`` in epoch ``e``'s cluster."""
        newest = -1
        for at in self.history.get(pos, ()):
            if at > e:
                break
            newest = at
        return pos if newest < 0 else version_id(self.n, newest, pos)

    def pass_problems(self, e: int, got, limit: int) -> dict:
        """How many constraints of pass ``got`` over epoch ``e``'s cluster
        report another total than owed (``totals``) or keep fewer than
        ``min(limit, total)`` or more than ``limit`` violations
        (``kept_short``), and how many kept violations name an object that
        cluster does not hold, a replaced Pod or one not yet made
        (``kept_stale``), or a changed object in a version the device did
        not find violating (``kept_unfounded``)."""
        out = {"totals": 0, "kept_short": 0, "kept_stale": 0,
               "kept_unfounded": 0}
        owed = self.totals[e]
        for key in set(owed) | set(got.total_violations):
            total = got.total_violations.get(key, 0)
            out["totals"] += int(total != owed.get(key, 0))
            kept = len(got.kept.get(key, ()))
            out["kept_short"] += int(not min(limit, total) <= kept <= limit)
        for key, kept in got.kept.items():
            for v in kept:
                name = (v.kind, v.namespace, v.name)
                if not self.born.get(name, -1) <= e < self.gone.get(
                        name, len(self.totals)):
                    out["kept_stale"] += 1
                elif name in self.position:
                    vid = self.version(e, self.position[name])
                    out["kept_unfounded"] += int(
                        tuple(key) not in self.violated.get(vid, ()))
        return out

    def kept_versions(self, e: int, got, unchanged: dict) -> tuple:
        """({(kind, namespace, name): the bytes listed under that name in
        epoch ``e``'s cluster} for every object pass ``got`` keeps a
        violation of, the names no listed object has).  ``unchanged``:
        ``locate``'s answer for the kept names no epoch changed."""
        out = {}
        for name in kept_names(got):
            if name in self.position:
                out[name] = self.raw_of[self.version(e, self.position[name])]
            elif name in unchanged:
                out[name] = unchanged[name][1]
        return out, kept_names(got) - set(out)


def kept_names(got) -> set:
    return {(v.kind, v.namespace, v.name)
            for kept in got.kept.values() for v in kept}


def kept_message_problems(got, versions: dict, results: dict) -> int:
    """Constraints of pass ``got`` whose kept violations are not results
    of the interpreter's review of the very bytes listed.  ``versions``:
    name -> bytes; ``results``: bytes -> {constraint key: [messages]}."""
    bad = 0
    for key, kept in got.kept.items():
        owed: dict = {}
        ok = True
        for v in kept:
            name = (v.kind, v.namespace, v.name)
            if name not in versions:
                ok = False
                break
            if name not in owed:
                owed[name] = list(results.get(versions[name], {}).get(
                    tuple(key), ()))
            if v.message not in owed[name]:
                ok = False
                break
            owed[name].remove(v.message)
        bad += int(not ok)
    return bad


if __name__ == "__main__":
    # a range of epochs, in a process of its own
    with open(sys.argv[1]) as _f:
        write_epochs(**json.load(_f))
