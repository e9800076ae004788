"""Differential tests for CEL → device lowering (ir/lower_cel.py): the
fused verdict grid must agree with the CEL evaluator on every
(object, constraint) pair — including CEL's error outcomes (failurePolicy
Fail: an erroring validation VIOLATES, and the lowered ``Not(t(E))`` form
must reproduce that)."""

import os
import random

from gatekeeper_tpu.apis.constraints import Constraint
from gatekeeper_tpu.apis.templates import ConstraintTemplate
from gatekeeper_tpu.drivers.cel_driver import CELDriver
from gatekeeper_tpu.drivers.tpu_driver import TpuDriver
from gatekeeper_tpu.target.review import AugmentedUnstructured
from gatekeeper_tpu.target.target import K8sValidationTarget
from gatekeeper_tpu.utils.unstructured import load_yaml_file

LIB = os.path.join(os.path.dirname(__file__), "..", "library", "general")
TARGET = "admission.k8s.gatekeeper.sh"


def _driver_with(*names):
    tpu = TpuDriver(batch_bucket=16, cel_driver=CELDriver())
    cons = []
    for name, params in names:
        tdoc = load_yaml_file(
            os.path.join(LIB, name, "template.yaml"))[0]
        t = ConstraintTemplate.from_unstructured(tdoc)
        tpu.add_template(t)
        cdoc = load_yaml_file(
            os.path.join(LIB, name, "samples", "constraint.yaml"))[0]
        if params is not None:
            cdoc.setdefault("spec", {})["parameters"] = params
            cdoc["metadata"]["name"] += "-alt"
        con = Constraint.from_unstructured(cdoc)
        tpu.add_constraint(con)
        cons.append(con)
    return tpu, cons


def _adversarial_pods(n, seed=7):
    """Objects probing CEL error semantics: mixed-type fields, missing
    guards' targets, unparseable quantities, non-bool privileged."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        containers = []
        for j in range(rng.randint(0, 3)):
            c = {"name": f"c{j}"}
            if rng.random() < 0.85:
                c["image"] = rng.choice([
                    "openpolicyagent/opa", "exempt/me:v1", "nginx",
                    "exempt/other", 7, True,
                ])
            if rng.random() < 0.7:
                r = rng.random()
                if r < 0.5:
                    c["resources"] = {"limits": {
                        "memory": rng.choice([
                            "512Mi", "2Gi", "1e3", "banana", 512, None,
                            "100m",
                        ]),
                    }}
                elif r < 0.7:
                    c["resources"] = {"limits": {}}
                elif r < 0.85:
                    c["resources"] = {}
                else:
                    c["resources"] = rng.choice(["notadict", 5])
            if rng.random() < 0.5:
                c["securityContext"] = {
                    "privileged": rng.choice(
                        [True, False, "yes", 1, None]),
                }
            elif rng.random() < 0.2:
                c["securityContext"] = rng.choice([{}, "bad"])
            containers.append(c)
        spec = {}
        if rng.random() < 0.9:
            spec["containers"] = containers
        if rng.random() < 0.25:
            spec["initContainers"] = [
                {"name": "init",
                 "securityContext": {"privileged": rng.random() < 0.5},
                 "image": "init/image"},
            ]
        obj = {"apiVersion": "v1", "kind": "Pod",
               "metadata": {"name": f"p{i}"}}
        if rng.random() < 0.95:
            obj["spec"] = spec
        out.append(obj)
    return out


def _assert_agreement(tpu, cons, objects):
    target = K8sValidationTarget()
    reviews = [target.handle_review(AugmentedUnstructured(object=o))
               for o in objects]
    got = tpu.query_batch(TARGET, cons, reviews)
    cel = tpu._cel
    for oi, review in enumerate(reviews):
        expected = []
        for con in cons:
            if not target.to_matcher(con.match).match(review):
                continue
            expected.extend(cel.query(TARGET, [con], review).results)
        key = lambda r: (r.constraint["metadata"]["name"], r.msg)
        assert sorted(map(key, got[oi].results)) == \
            sorted(map(key, expected)), (
                f"divergence on object {oi}: {objects[oi]}\n"
                f"got={sorted(map(key, got[oi].results))}\n"
                f"want={sorted(map(key, expected))}")


def test_cel_library_templates_lower():
    tpu, _ = _driver_with(("noprivileged", None),
                          ("containerlimitscel", None))
    assert set(tpu.lowered_kinds()) == {
        "K8sNoPrivileged", "K8sContainerLimitsCEL"}
    assert not tpu.fallback_kinds()


def test_cel_differential_library_sample_params():
    tpu, cons = _driver_with(("noprivileged", None),
                             ("containerlimitscel", None))
    _assert_agreement(tpu, cons, _adversarial_pods(250))


def test_cel_differential_alt_params():
    # exemptImages exercised; memory param absent (the !has(params.memory)
    # arm) and present-but-unparseable
    tpu, cons = _driver_with(
        ("noprivileged", {"exemptImages": ["exempt/"]}),
        ("containerlimitscel", {}),
    )
    _assert_agreement(tpu, cons, _adversarial_pods(250, seed=11))
    tpu2, cons2 = _driver_with(
        ("noprivileged", {"exemptImages": []}),
        ("containerlimitscel", {"memory": "banana"}),
    )
    _assert_agreement(tpu2, cons2, _adversarial_pods(150, seed=13))


def test_cel_library_suites_still_pass_with_unified_driver():
    """gator verify suites for the CEL library entries, through a client
    whose TpuDriver owns the CEL templates."""
    from gatekeeper_tpu.gator import verify as verify_mod

    for name in ("noprivileged", "containerlimitscel"):
        sr = verify_mod.run_suite(os.path.join(LIB, name, "suite.yaml"))
        assert not sr.failed(), [
            (t.name, c.name, c.error) for t in sr.tests for c in t.cases
            if c.error
        ]


def test_cel_delete_reviews_route_to_evaluator():
    """DELETE admission reviews diverge for CEL kinds (object unset for the
    evaluator while the grid sees the copied oldObject): query_batch must
    agree with the evaluator's DELETE semantics."""
    from gatekeeper_tpu.target.review import AdmissionRequest, AugmentedReview

    tpu, cons = _driver_with(("containerlimitscel", None))
    target = K8sValidationTarget()
    bad = {"apiVersion": "v1", "kind": "Pod",
           "metadata": {"name": "del-me"},
           "spec": {"containers": [{"name": "c"}]}}
    req = AdmissionRequest(
        uid="u", kind={"group": "", "version": "v1", "kind": "Pod"},
        resource={}, sub_resource="", name="del-me", namespace="",
        operation="DELETE", user_info={}, object=None, old_object=bad,
        dry_run=False, options=None,
    )
    review = target.handle_review(AugmentedReview(admission_request=req))
    got = tpu.query_batch(TARGET, cons, [review])
    want = tpu._cel.query(TARGET, cons, review)
    assert sorted(r.msg for r in got[0].results) == \
        sorted(r.msg for r in want.results)
    assert got[0].results  # the old object violates (no memory limit)


def _mini_cel(source_yaml_validations, kind="K8sCelMini", params_schema=None):
    import yaml as _yaml

    tpu = TpuDriver(batch_bucket=16, cel_driver=CELDriver())
    doc = {
        "apiVersion": "templates.gatekeeper.sh/v1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind},
                             "validation": {"openAPIV3Schema":
                                            params_schema or
                                            {"type": "object"}}}},
            "targets": [{
                "target": TARGET,
                "code": [{"engine": "K8sNativeValidation",
                          "source": _yaml.safe_load(
                              source_yaml_validations)}],
            }],
        },
    }
    t = ConstraintTemplate.from_unstructured(doc)
    tpu.add_template(t)
    con = Constraint.from_unstructured({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": kind, "metadata": {"name": "mini"},
        "spec": {},
    })
    tpu.add_constraint(con)
    return tpu, con


def test_cel_heterogeneous_inequality_is_defined_false():
    """CEL `!=` on mixed types is a DEFINED true (heterogeneous equality),
    not an error — a non-string field must not produce a phantom hit."""
    tpu, con = _mini_cel("""
validations:
  - expression: 'object.spec.tier != "forbidden"'
    message: tier forbidden
""", kind="K8sCelNeq")
    assert "K8sCelNeq" in tpu.lowered_kinds(), tpu.fallback_kinds()
    objs = [
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "n"},
         "spec": {"tier": 3}},                     # mixed type: != is true
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "f"},
         "spec": {"tier": "forbidden"}},           # violates
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "ok"},
         "spec": {"tier": "gold"}},                # fine
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "ab"},
         "spec": {}},                              # absent: error: violates
    ]
    _assert_agreement(tpu, [con], objs)


def test_cel_bool_and_num_equality_heterogeneous():
    tpu, con = _mini_cel("""
validations:
  - expression: 'object.spec.flag == true || object.spec.count == 3.0'
    message: bad
""", kind="K8sCelHet")
    assert "K8sCelHet" in tpu.lowered_kinds(), tpu.fallback_kinds()
    objs = [
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "a"},
         "spec": {"flag": "yes", "count": "3"}},   # both mixed: false||false
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "b"},
         "spec": {"flag": True, "count": 0}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "c"},
         "spec": {"flag": False, "count": 3}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "d"},
         "spec": {"flag": None}},                  # count absent: || error
    ]
    _assert_agreement(tpu, [con], objs)


def test_cel_var_free_macro_body_lowers_via_map_branch():
    """A macro whose body never dereferences the loop variable evaluates
    fine over map KEYS — the kind-branched map lowering (item-independent
    body under the key binding) now represents that exactly, so the
    template stays on the device (it fell back before round 3)."""
    tpu, con = _mini_cel("""
validations:
  - expression: >-
      !has(object.metadata.annotations) ? true :
      object.metadata.annotations.all(a, has(object.spec.ok))
    message: bad
""", kind="K8sCelKeys")
    assert "K8sCelKeys" in tpu.lowered_kinds(), tpu.fallback_kinds()
    objs = [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "m", "annotations": {"k1": "v", "k2": "v"}},
         "spec": {"ok": True}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "n", "annotations": {"k": "v"}}, "spec": {}},
    ]
    _assert_agreement(tpu, [con], objs)


def test_cel_absorbed_deref_falls_back():
    """`has(c.x) || true` is TRUE over map keys (absorbed error): bodies
    whose outcome can be decided without dereferencing the variable must
    not lower."""
    tpu, _con = _mini_cel("""
variables:
  - name: containers
    expression: >-
      !has(object.spec.containers) ? [] : object.spec.containers
validations:
  - expression: 'variables.containers.all(c, has(c.image) || true)'
    message: bad
""", kind="K8sCelAbsorb")
    assert "K8sCelAbsorb" in tpu.fallback_kinds()


def test_cel_object_macro_nested_in_param_macro():
    """ADVICE r2 (high): a StrPred needle under AnyAxis inside a
    param-list macro (object-list macro nested in a param-list macro)
    must either lower with its needle bound — evaluating the [N, M, K]
    grid — or fall back at add_template time.  It must NEVER lower
    'successfully' into a program that raises on every query."""
    import yaml as _yaml

    kind = "K8sCelNestedElem"
    tpu = TpuDriver(batch_bucket=16, cel_driver=CELDriver())
    doc = {
        "apiVersion": "templates.gatekeeper.sh/v1",
        "kind": "ConstraintTemplate",
        "metadata": {"name": kind.lower()},
        "spec": {
            "crd": {"spec": {"names": {"kind": kind},
                             "validation": {"openAPIV3Schema": {
                                 "type": "object",
                                 "properties": {"prefixes": {
                                     "type": "array",
                                     "items": {"type": "string"}}}}}}},
            "targets": [{
                "target": TARGET,
                "code": [{"engine": "K8sNativeValidation",
                          "source": _yaml.safe_load("""
validations:
  - expression: >-
      params.prefixes.exists(p,
      object.spec.containers.all(c, c.image.startsWith(p)))
    message: no common registry prefix
""")}],
            }],
        },
    }
    tpu.add_template(ConstraintTemplate.from_unstructured(doc))
    con = Constraint.from_unstructured({
        "apiVersion": "constraints.gatekeeper.sh/v1beta1",
        "kind": kind, "metadata": {"name": "nested"},
        "spec": {"parameters": {"prefixes": ["good/", "ok-"]}},
    })
    tpu.add_constraint(con)
    objs = [
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "a"},
         "spec": {"containers": [{"name": "c", "image": "good/x"},
                                 {"name": "d", "image": "good/y"}]}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "b"},
         "spec": {"containers": [{"name": "c", "image": "good/x"},
                                 {"name": "d", "image": "bad/y"}]}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "c"},
         "spec": {"containers": [{"name": "c", "image": "ok-1"}]}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "d"},
         "spec": {"containers": []}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "e"},
         "spec": {}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "f"},
         "spec": {"containers": [{"name": "c", "image": 7}]}},
    ]
    # whichever way it resolved (device or fallback), verdicts must match
    # the CEL oracle — and queries must not raise
    _assert_agreement(tpu, [con], objs)
    # with the AnyAxis recursion the template should stay on the device
    assert kind in tpu.lowered_kinds(), tpu.fallback_kinds()


def test_cel_map_key_predicate_body_lowers():
    """Map-key predicate bodies (`annotations.exists(k, k.startsWith(p))`)
    lower to string ops over the MapKeyColumn, kind-branched so LIST
    values keep list semantics (VERDICT r2 missing #2)."""
    tpu, con = _mini_cel("""
validations:
  - expression: '!object.metadata.annotations.exists(k, k.startsWith("seccomp."))'
    message: no seccomp annotations allowed
""", kind="K8sCelMapKey")
    assert "K8sCelMapKey" in tpu.lowered_kinds(), tpu.fallback_kinds()
    meta = lambda name, ann: {"name": name, **({"annotations": ann}
                                               if ann is not None else {})}
    objs = [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("hit", {"seccomp.alpha": "x", "other": "y"})},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("miss", {"app": "x"})},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("empty", {})},     # vacuous exists -> false -> ok
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("absent", None)},  # error -> violation
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("alist", ["seccomp.alpha"])},  # LIST: items
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("scalar", "notamap")},  # error -> violation
    ]
    _assert_agreement(tpu, [con], objs)


def test_cel_exists_one_lowers():
    """exists_one: exactly-one semantics with no short-circuit — any
    erroring item errors the whole macro (VERDICT r2 missing #2)."""
    tpu, con = _mini_cel("""
validations:
  - expression: 'object.spec.containers.exists_one(c, c.name == "main")'
    message: need exactly one main container
""", kind="K8sCelExistsOne")
    assert "K8sCelExistsOne" in tpu.lowered_kinds(), tpu.fallback_kinds()
    pod = lambda name, cs: {"apiVersion": "v1", "kind": "Pod",
                            "metadata": {"name": name},
                            "spec": {"containers": cs}}
    objs = [
        pod("zero", [{"name": "a"}, {"name": "b"}]),      # 0 -> violation
        pod("one", [{"name": "main"}, {"name": "b"}]),    # 1 -> ok
        pod("two", [{"name": "main"}, {"name": "main"}]), # 2 -> violation
        pod("err", [{"name": "main"}, {}]),  # missing name: heterogeneous
        pod("empty", []),                                 # 0 -> violation
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "nolist"}, "spec": {}},     # error
    ]
    _assert_agreement(tpu, [con], objs)


def test_cel_two_variable_map_macro():
    """Two-variable macros: over a map (key, value) the key binds to the
    MapKeyColumn; over a LIST, CEL binds (index, value) and the
    string-method body errors per item, so the list branch reduces to
    vacuous/error (VERDICT r2 missing #2)."""
    tpu, con = _mini_cel("""
validations:
  - expression: 'object.metadata.labels.all(k, v, !k.startsWith("forbidden."))'
    message: forbidden label prefix
""", kind="K8sCelTwoVar")
    assert "K8sCelTwoVar" in tpu.lowered_kinds(), tpu.fallback_kinds()
    meta = lambda name, labels: {"name": name, **({"labels": labels}
                                                  if labels is not None
                                                  else {})}
    objs = [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("hit", {"forbidden.x": "1", "app": "a"})},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("ok", {"app": "a"})},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("empty", {})},    # vacuous all -> true -> ok
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("absent", None)},  # error -> violation
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": meta("alist", ["x"])},  # int keys: error -> violation
    ]
    _assert_agreement(tpu, [con], objs)


def test_cel_two_variable_value_body_has_list_semantics():
    """A two-variable body that can decide from the VALUE alone has real
    list semantics (index keys don't error it): the list branch binds the
    index to a value on which only string methods err, so it lowers and
    agrees with the oracle on maps and on lists."""
    tpu, con = _mini_cel("""
validations:
  - expression: 'object.metadata.labels.all(k, v, v != "")'
    message: empty label value
""", kind="K8sCelTwoVarVal")
    assert "K8sCelTwoVarVal" in tpu.lowered_kinds(), tpu.fallback_kinds()
    objs = [
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "a", "labels": {"x": ""}}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "b", "labels": {"x": "1"}}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "c", "labels": ["1", ""]}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "d", "labels": ["1", 2]}},
        {"apiVersion": "v1", "kind": "Pod",
         "metadata": {"name": "e", "labels": []}},
        {"apiVersion": "v1", "kind": "Pod", "metadata": {"name": "f"}},
    ]
    _assert_agreement(tpu, [con], objs)
