"""Kind routing of a listed object stream into per-group chunks.

The audit's relist sweep and the evaluator's warm-up both turn "every
object the lister yields" into "chunks of one kind group each"
(``parallel/sharded.make_kind_router``).  That is per-object work on the
pass's calling thread, under the GIL every stage behind it shares, so it
runs as one native call per chunk (``native/listroutemod.c``) wherever
the module builds: the call reads the kind from the head of an unloaded
``RawJSON``'s bytes and asks ``peek_kind`` for every object whose head
settles nothing.  On the way it takes every unloaded, still empty
``RawJSON`` off the cyclic collector's lists (such an object can be part
of no cycle; ``utils/rawjson`` puts it back the moment it loads).
Besides the head, the same module reads a kept violation's identity off
such an object's bytes for the audit fold (``identity()``, behind
``utils/rawjson.peek_identity``): apiVersion, kind, metadata.name and
metadata.namespace in one validating pass over the whole document, so
that the fold loads no object to name it.
:func:`route_chunks_py` is the per-object loop it replaces: the fallback,
and the reference the native call is tested against
(``tests/test_list_routing.py``); its objects stay tracked.
"""

from __future__ import annotations

from gatekeeper_tpu.utils.rawjson import peek_kind


def route_chunks(objects, router, chunk_size, counter, counts,
                 kind_filter=None, tee=None):
    """Yield ``(group, chunk)`` in the one canonical order: a group's
    chunk the moment it holds ``chunk_size`` objects, and once
    ``objects`` ends every group's partial chunk, in the order the groups
    were first seen.  Objects whose kind ``kind_filter`` (a container of
    kinds, or None) leaves out are dropped uncounted; ``counter[0]``
    grows by every other object, routed or not (an empty group: no
    template reaches the kind).  ``counts[0]`` grows by the objects the
    native call settled by itself, ``counts[1]`` by those that went
    through ``peek_kind``, one at a time, and ``counts[2]`` by those the
    native call took off the cyclic collector's lists.

    ``tee(obj, kind)``, if given, sees every object with its kind before
    the filter; such a stream stays on the per-object loop."""
    mod = None
    if tee is None:
        from gatekeeper_tpu.ops import native

        mod = native.load_listroute()
    if mod is None:
        yield from route_chunks_py(objects, router, chunk_size, counter,
                                   counts, kind_filter, tee)
        return

    def entry_of(kind):
        # what the native call keeps per kind: None drops the object
        # uncounted, an empty group drops it counted
        if kind_filter is not None and kind not in kind_filter:
            return None
        return router(kind)

    it = iter(objects)
    bufs: dict = {}   # group -> pending chunk
    kinds: dict = {}  # kind bytes -> entry_of(kind), filled by the call
    while True:
        g = mod.route(it, bufs, kinds, chunk_size, peek_kind, entry_of,
                      counter, counts)
        if g is None:
            break
        yield g, bufs[g]
        bufs[g] = []
    for g, buf in bufs.items():
        if buf:
            yield g, buf


def route_chunks_py(objects, router, chunk_size, counter, counts,
                    kind_filter=None, tee=None):
    """The per-object loop: what :func:`route_chunks` means."""
    bufs: dict = {}  # group -> pending chunk
    for obj in objects:
        counts[1] += 1
        k = peek_kind(obj)
        if tee is not None:
            tee(obj, k)
        if kind_filter is not None and k not in kind_filter:
            continue
        counter[0] += 1
        g = router(k)
        if not g:
            continue  # no template's match reaches this kind
        buf = bufs.setdefault(g, [])
        buf.append(obj)
        if len(buf) >= chunk_size:
            yield g, buf
            bufs[g] = []
    for g, buf in bufs.items():
        if buf:
            yield g, buf
