"""``benchmark/wiring.py`` passes the audit plane what a configuration
defines and names no lane option of the program (ROADMAP D0, PR 31), so a
later PR that deletes one of those keywords breaks no cell.  Until PR 31 it
passed seven by keyword, each at the value below; what is held here is that
leaving them out runs the same program: each is still its constructor's
default, or is gone from the signature altogether."""

from __future__ import annotations

import ast
import inspect
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import wiring  # noqa: E402

# constructor -> {keyword the wiring passed until PR 31: the value}
DROPPED = {
    "ShardedEvaluator": {"flatten_lane": "auto", "collect": "reduced",
                         "flatten_workers": 0},
    "AuditConfig": {"pipeline": "auto", "pipeline_flatten_workers": 0,
                    "shard_chunks": 0, "audit_source": "relist"},
}
# what a cell's configuration defines (its ``audit`` block), and the wiring;
# ``expand_generated`` is ``--audit-expand``, false where a
# configuration leaves it out, as it is ``AuditConfig``'s default
KEPT = {
    "ShardedEvaluator": {"violations_limit", "metrics"},
    "AuditConfig": {"interval_s", "violations_limit", "chunk_size",
                    "exact_totals", "expand_generated"},
}


def constructor(name: str):
    from gatekeeper_tpu.audit.manager import AuditConfig
    from gatekeeper_tpu.parallel.sharded import ShardedEvaluator

    return {"ShardedEvaluator": ShardedEvaluator,
            "AuditConfig": AuditConfig}[name]


def test_a_configuration_that_names_no_expansion_runs_the_default():
    from gatekeeper_tpu.audit.manager import AuditConfig

    params = inspect.signature(AuditConfig).parameters
    assert params["expand_generated"].default is False
    source = inspect.getsource(wiring.Program.build_audit)
    assert 'a.get("expand_generated", False)' in source


@pytest.mark.parametrize("name,keyword", [
    (name, kw) for name, kws in DROPPED.items() for kw in kws])
def test_a_dropped_keyword_defaults_to_what_the_wiring_passed(name, keyword):
    params = inspect.signature(constructor(name)).parameters
    if keyword in params:  # a PR that deleted the option has nothing to hold
        assert params[keyword].default == DROPPED[name][keyword]


def test_the_wiring_names_what_a_configuration_defines_and_no_lane_option():
    tree = ast.parse(inspect.getsource(wiring.Program.build_audit).lstrip())
    seen = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(
                node.func, "id", None) in KEPT:
            seen[node.func.id] = {k.arg for k in node.keywords}
    assert seen == KEPT
    source = inspect.getsource(wiring)
    for keywords in DROPPED.values():
        for keyword in keywords:
            assert keyword + "=" not in source, keyword
