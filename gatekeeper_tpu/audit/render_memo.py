"""The audit's render memo: last pass's messages for what did not change.

Audit is periodic over a cluster that mostly stays as it was between
passes, and a render is a pure function of its key (the driver's
``render_token`` for the constraint, the review's ``source``, the bytes of
the object): the memo answers an unchanged pair with the list of
``Result`` the interpreter returned for it a pass ago.

Two generations bound it by what a pass asks for: a lookup tries this
pass's table, then the last pass's (and moves the entry over); at the end
of a pass the last pass's table is dropped, so what no pass asks for
again is gone after one pass.  An insert is skipped once this pass's
table is full (``begin_pass`` sets the cap from the constraints and the
kept-violations limit), so the ``exact_totals`` lane, which renders every
hit, cannot pin a cluster's worth of object bytes.
"""

from __future__ import annotations


class RenderMemo:
    __slots__ = ("cur", "prev", "cap")

    # entries a pass may hold per kept violation the run can report
    PER_KEPT = 2

    def __init__(self):
        self.cur: dict = {}
        self.prev: dict = {}
        self.cap = 0

    def begin_pass(self, n_constraints: int, violations_limit: int) -> None:
        self.cap = self.PER_KEPT * n_constraints * max(1, violations_limit)

    def end_pass(self) -> None:
        self.prev = self.cur
        self.cur = {}

    def get(self, key):
        results = self.cur.get(key)
        if results is None:
            results = self.prev.get(key)
            if results is not None:
                self.put(key, results)
        return results

    def put(self, key, results) -> None:
        if len(self.cur) < self.cap:
            self.cur[key] = results

    def __len__(self) -> int:
        return len(self.cur) + len(self.prev)
