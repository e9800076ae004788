"""The ``library-cel`` library: the stock library as Gatekeeper's default
engine evaluates it.  ``main.go:465-485`` registers the k8scel driver ahead of
the Rego driver, and gatekeeper-library ships a ``K8sNativeValidation`` code
block beside the Rego of most of ``library/general`` and
``library/pod-security-policy``, so a stock Gatekeeper evaluates most of the
stock library through CEL.

    python3 benchmark/libraries/make_cel.py [--out DIR]

writes ``benchmark/libraries/cel/`` anew: one directory a template
(``template.yaml``, ``samples/constraint.yaml``) for each of the 36 kinds of
``POLICIES`` (``tests/benchmark/test_library_cel.py`` holds the committed
files to this script, byte for byte).  The two CEL templates ``library/``
already has (``general/containerlimitscel``, ``general/noprivileged``) and
the eight kinds of ``REGO_KEPT`` stay where they are; the configuration names
them by their stock paths.

Upstream's blocks are not in this repository and nothing is fetched.  Each
block below is written here, in upstream's idiom, to state the policy of this
repository's own Rego template of the same kind (``tests/test_cel_library.py``
holds the two engines to the same violating objects):

- the template keeps its name, kind, description and parameter schema, and
  its target carries a ``code: - engine: K8sNativeValidation`` block and no
  Rego (this program's client takes the Rego block of a template that has
  both; upstream takes the CEL one);
- ``samples/constraint.yaml`` is the stock sample, copied unchanged;
- Pod-scope kinds bind ``variables.containers``, ``.initContainers`` (and
  ``.ephemeralContainers`` where the Rego walks them) as ``has(x) ? x : []``,
  collect ``variables.badContainers`` with ``.filter(container, ...).map(
  container, "<message> " + container.name)`` and validate
  ``size(variables.badContainers) == 0`` with ``messageExpression:
  variables.badContainers.join("\\n")``; no Rego of the library skips
  updates, so no validation reads ``request.operation``;
- where the Rego exempts images by ``exemptImages`` with a trailing ``*``
  (``containerresources``, ``disallowinteractivetty``) the three variables
  ``exemptImagePrefixes``, ``exemptImageExplicit`` and ``exemptImages``;
  ``allowprivilegeescalation``'s Rego exempts by plain prefix, and so does
  its block;
- parameters through ``variables.params``; ``failurePolicy: Fail``; no
  ``matchConditions``.
"""

from __future__ import annotations

import argparse
import os
import shutil

import yaml

HERE = os.path.dirname(os.path.abspath(__file__))
LIBRARY = os.path.join(os.path.dirname(os.path.dirname(HERE)), "library")

# kept on their Rego template of library/, and why
REGO_KEPT = {
    "general/storageclass": "data.inventory; CEL has none",
    "general/uniqueingresshost": "data.inventory; CEL has none",
    "general/uniqueserviceselector": "data.inventory; CEL has none",
    "general/noupdateserviceaccount": "oldObject and userInfo",
    "general/containerresourceratios":
        "a quotient of quantities; the quantity library has no division",
    "general/verifydeprecatedapi": "Rego-only upstream",
    "general/horizontalpodautoscaler":
        "referential upstream; the cluster lists no such object",
    "general/poddisruptionbudget":
        "referential upstream; the cluster lists no such object",
}
# CEL already, in library/
CEL_STOCK = ["general/containerlimitscel", "general/noprivileged"]

OBJ = "variables.anyObject"
SPEC = OBJ + ".spec"
META = OBJ + ".metadata"


def _list_var(name: str, path: str) -> tuple:
    return name, f"has({path}) ? {path} : []"


CONTAINERS = _list_var("containers", SPEC + ".containers")
INIT = _list_var("initContainers", SPEC + ".initContainers")
EPHEMERAL = _list_var("ephemeralContainers", SPEC + ".ephemeralContainers")
VOLUMES = _list_var("volumes", SPEC + ".volumes")
TWO = "(variables.containers + variables.initContainers)"
THREE = ("(variables.containers + variables.initContainers + "
         "variables.ephemeralContainers)")


def _param_list(name: str, param: str) -> tuple:
    return _list_var(name, "variables.params." + param)


def _exempt_vars(lists: str) -> list:
    """Upstream's three variables for ``exemptImages`` with a trailing *."""
    return [
        ("exemptImagePrefixes",
         "!has(variables.params.exemptImages) ? [] : "
         "variables.params.exemptImages.filter(image, image.endsWith(\"*\"))"
         ".map(image, string(image).replace(\"*\", \"\"))"),
        ("exemptImageExplicit",
         "!has(variables.params.exemptImages) ? [] : "
         "variables.params.exemptImages.filter(image, "
         "!image.endsWith(\"*\"))"),
        ("exemptImages",
         f"{lists}.filter(container, "
         "container.image in variables.exemptImageExplicit || "
         "variables.exemptImagePrefixes.exists(exemption, "
         "container.image.startsWith(exemption)))"
         ".map(container, container.image)"),
    ]


def _bad(lists: str, cond: str, message: str) -> tuple:
    return ("badContainers",
            f"{lists}.filter(container, {cond}).map(container, {message})")


BAD_VALIDATION = [{
    "expression": "size(variables.badContainers) == 0",
    "messageExpression": 'variables.badContainers.join("\\n")',
}]

_SC = "container.securityContext"
_HAS_SC = f"has({_SC})"


def _quantity_over(where: str, what: str, key: str) -> str:
    """``where`` (limits, requests) of ``what`` above the parameter."""
    return (f"(has(variables.params.{key}) && "
            f"quantity(container.resources.{where}.{what})"
            f".isGreaterThan(quantity(variables.params.{key})))")


def _limits_or_requests(where: str, noun: str) -> dict:
    res = "container.resources"
    cond = (f"!has({res}) || !has({res}.{where}) || "
            f"!has({res}.{where}.memory) || !has({res}.{where}.cpu) || "
            + _quantity_over(where, "memory", "memory") + " || "
            + _quantity_over(where, "cpu", "cpu"))
    return {
        "variables": [CONTAINERS, INIT, _bad(
            TWO, cond,
            f'"container <" + container.name + "> has no memory or cpu '
            f'{noun}, or one above the maximum allowed"')],
        "validations": BAD_VALIDATION,
    }


def _in_ranges(value: str, ranges: str) -> str:
    return (f"{ranges}.exists(r, r.min <= {value} && {value} <= r.max)")


_SELINUX = " && ".join(
    f"a.{f} == {{0}}.seLinuxOptions.{f}"
    for f in ("level", "role", "type", "user"))
_POD_SC = SPEC + ".securityContext"
_HAS_POD_SC = f"has({_POD_SC})"
_RESOURCES = ("!has(container.resources) || "
              "!has(container.resources.limits) || "
              "!has(container.resources.limits.memory) || "
              "!has(container.resources.limits.cpu) || "
              "!has(container.resources.requests) || "
              "!has(container.resources.requests.memory) || "
              "!has(container.resources.requests.cpu)")
_APPARMOR = "container.apparmor.security.beta.kubernetes.io/"
_ALLOW_HTTP = "kubernetes.io/ingress.allow-http"
_USER = _SC + ".runAsUser"
_POD_USER = _POD_SC + ".runAsUser"
_SECCOMP = _SC + ".seccompProfile"
_POD_SECCOMP = _POD_SC + ".seccompProfile"
_USER_RANGES = "variables.params.runAsUser.ranges"


def _required(what: str, noun: str) -> dict:
    """requiredlabels / requiredannotations: ``what`` is the map's field
    under metadata and the parameter's name."""
    m = f"{META}.{what}"
    return {
        "variables": [
            _param_list("required", what),
            ("missing",
             f"variables.required.filter(l, !has({m}) || !(l.key in {m}))"
             ".map(l, l.key)"),
        ],
        "validations": [
            {"expression": "size(variables.missing) == 0",
             "messageExpression":
                 f'"you must provide {what}: " + '
                 'variables.missing.join(", ")'},
            {"expression":
                 "variables.required.all(l, !has(l.allowedRegex) || "
                 f'l.allowedRegex == "" || !has({m}) || !(l.key in {m}) || '
                 f"{m}[l.key].matches(l.allowedRegex))",
             "message": f"{noun} does not satisfy its allowed regex"},
        ],
    }


# directory under benchmark/libraries/cel -> (area of library/, source)
POLICIES = {
    "allowedrepos": ("general", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            "!variables.params.repos.exists(repo, "
            "container.image.startsWith(repo))",
            '"container <" + container.name + "> has an invalid image repo <"'
            ' + container.image + ">, allowed repos are " + '
            'variables.params.repos.join(", ")')],
        "validations": BAD_VALIDATION,
    }),
    "automounttoken": ("general", {
        "validations": [{
            "expression":
                f"has({SPEC}.automountServiceAccountToken) && "
                f"{SPEC}.automountServiceAccountToken == false",
            "messageExpression":
                f'"pod <" + {META}.name + "> mounts the service account '
                'token by default"',
        }],
    }),
    "blockendpointeditdefaultrole": ("general", {
        "validations": [{
            "expression":
                f'{META}.name != "system:aggregate-to-edit" || '
                f"!has({OBJ}.rules) || !{OBJ}.rules.exists(rule, "
                "has(rule.resources) && has(rule.verbs) && "
                '"endpoints" in rule.resources && '
                "rule.verbs.exists(verb, "
                'verb in ["create", "patch", "update"]))',
            "message":
                "ClusterRole system:aggregate-to-edit must not allow "
                "create/patch/update of endpoints (CVE-2021-25740)",
        }],
    }),
    "blockloadbalancer": ("general", {
        "validations": [{
            "expression":
                f'!(has({SPEC}.type) && {SPEC}.type == "LoadBalancer")',
            "message":
                "User is not allowed to create service of type LoadBalancer",
        }],
    }),
    "blocknodeport": ("general", {
        "validations": [{
            "expression":
                f'!(has({SPEC}.type) && {SPEC}.type == "NodePort")',
            "message":
                "User is not allowed to create service of type NodePort",
        }],
    }),
    "blockwildcardingress": ("general", {
        "variables": [_list_var("rules", SPEC + ".rules")],
        "validations": [
            {"expression":
                 "variables.rules.all(rule, !has(rule.host) || "
                 '!rule.host.contains("*"))',
             "message": "ingress host contains a wildcard"},
            {"expression":
                 "size(variables.rules) == 0 || "
                 "variables.rules.exists(rule, has(rule.host))",
             "message": "ingress rule with no host defaults to a wildcard"},
        ],
    }),
    "capabilities": ("general", {
        "variables": [
            CONTAINERS, INIT,
            _param_list("allowedCapabilities", "allowedCapabilities"),
            _param_list("requiredDropCapabilities",
                        "requiredDropCapabilities"),
            _bad(TWO,
                 f"({_HAS_SC} && has({_SC}.capabilities) && "
                 f"has({_SC}.capabilities.add) && "
                 '!("*" in variables.allowedCapabilities) && '
                 f"!{_SC}.capabilities.add.all(cap, "
                 "cap in variables.allowedCapabilities)) || "
                 "!variables.requiredDropCapabilities.all(cap, "
                 f"{_HAS_SC} && has({_SC}.capabilities) && "
                 f"has({_SC}.capabilities.drop) && "
                 f"(cap in {_SC}.capabilities.drop || "
                 f'"ALL" in {_SC}.capabilities.drop))',
                 '"container <" + container.name + "> adds a disallowed '
                 'capability or does not drop a required one"')],
        "validations": BAD_VALIDATION,
    }),
    "containerlimits": ("general", _limits_or_requests("limits", "limit")),
    "containerrequests": ("general",
                          _limits_or_requests("requests", "request")),
    "containerresources": ("general", {
        "variables": [CONTAINERS, INIT, EPHEMERAL] + _exempt_vars(THREE) + [
            _bad(THREE,
                 "!(container.image in variables.exemptImages) && "
                 f"({_RESOURCES})",
                 '"container <" + container.name + "> does not have memory '
                 'and cpu limits and requests defined"')],
        "validations": BAD_VALIDATION,
    }),
    "disallowanonymous": ("general", {
        "validations": [{
            "expression":
                f"!has({OBJ}.subjects) || !{OBJ}.subjects.exists(subject, "
                "has(subject.name) && subject.name in "
                '["system:anonymous", "system:unauthenticated"])',
            "message": "binding to system:anonymous or "
                       "system:unauthenticated is not allowed",
        }],
    }),
    "disallowedrepos": ("general", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            "variables.params.repos.exists(repo, "
            "container.image.startsWith(repo))",
            '"container <" + container.name + "> has an image <" + '
            'container.image + "> from a disallowed repository"')],
        "validations": BAD_VALIDATION,
    }),
    "disallowedtags": ("general", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            '!container.image.contains(":") || '
            "variables.params.tags.exists(tag, "
            'container.image.endsWith(":" + tag))',
            '"container <" + container.name + "> uses a disallowed tag or '
            'no tag <" + container.image + ">"')],
        "validations": BAD_VALIDATION,
    }),
    "disallowinteractivetty": ("general", {
        "variables": [CONTAINERS, INIT, EPHEMERAL] + _exempt_vars(THREE) + [
            _bad(THREE,
                 "!(container.image in variables.exemptImages) && "
                 "((has(container.tty) && container.tty == true) || "
                 "(has(container.stdin) && container.stdin == true))",
                 '"container <" + container.name + "> is running in '
                 'interactive tty mode or with stdin attached, which is not '
                 'allowed"')],
        "validations": BAD_VALIDATION,
    }),
    "ephemeralstoragelimit": ("general", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            "!has(container.resources) || "
            "!has(container.resources.limits) || "
            '!("ephemeral-storage" in container.resources.limits) || '
            '("ephemeral-storage" in variables.params && '
            'quantity(container.resources.limits["ephemeral-storage"])'
            ".isGreaterThan(quantity("
            'variables.params["ephemeral-storage"])))',
            '"container <" + container.name + "> has no ephemeral-storage '
            'limit, or one above the maximum allowed"')],
        "validations": BAD_VALIDATION,
    }),
    "externalip": ("general", {
        "variables": [
            _param_list("allowedIPs", "allowedIPs"),
            _list_var("externalIPs", SPEC + ".externalIPs"),
            ("badIPs",
             "variables.externalIPs.filter(ip, "
             "!(ip in variables.allowedIPs))"
             '.map(ip, "externalIP <" + ip + "> is not allowed")'),
        ],
        "validations": [{
            "expression": "size(variables.badIPs) == 0",
            "messageExpression": 'variables.badIPs.join("\\n")',
        }],
    }),
    "forbiddensysctls": ("general", {
        "variables": [
            ("sysctls",
             f"!{_HAS_POD_SC} ? [] : !has({_POD_SC}.sysctls) ? [] : "
             f"{_POD_SC}.sysctls"),
            ("forbiddenPrefixes",
             "!has(variables.params.forbiddenSysctls) ? [] : "
             "variables.params.forbiddenSysctls.filter(s, "
             's.endsWith("*")).map(s, string(s).replace("*", ""))'),
            ("forbiddenExplicit",
             "!has(variables.params.forbiddenSysctls) ? [] : "
             "variables.params.forbiddenSysctls.filter(s, "
             '!s.endsWith("*"))'),
            ("badSysctls",
             "variables.sysctls.filter(sysctl, "
             "sysctl.name in variables.forbiddenExplicit || "
             "variables.forbiddenPrefixes.exists(prefix, "
             "sysctl.name.startsWith(prefix)))"
             '.map(sysctl, "sysctl <" + sysctl.name + "> is forbidden")'),
        ],
        "validations": [{
            "expression": "size(variables.badSysctls) == 0",
            "messageExpression": 'variables.badSysctls.join("\\n")',
        }],
    }),
    "hostfilesystem": ("general", {
        "variables": [
            VOLUMES,
            _param_list("allowedHostPaths", "allowedHostPaths"),
            ("badHostPaths",
             "variables.volumes.filter(volume, has(volume.hostPath) && "
             "has(volume.hostPath.path) && "
             "!variables.allowedHostPaths.exists(allowed, "
             "volume.hostPath.path.startsWith(allowed.pathPrefix)))"
             '.map(volume, "hostPath volume <" + volume.hostPath.path + '
             '"> is not allowed")'),
        ],
        "validations": [{
            "expression": "size(variables.badHostPaths) == 0",
            "messageExpression": 'variables.badHostPaths.join("\\n")',
        }],
    }),
    "hostnamespace": ("general", {
        "validations": [{
            "expression":
                f"!(has({SPEC}.hostPID) && {SPEC}.hostPID == true) && "
                f"!(has({SPEC}.hostIPC) && {SPEC}.hostIPC == true)",
            "messageExpression":
                '"Sharing the host namespace is not allowed: " + '
                f"{META}.name",
        }],
    }),
    "hostnetworkingports": ("general", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            "has(container.ports) && !container.ports.all(port, "
            "!has(port.hostPort) || "
            "(port.hostPort >= variables.params.min && "
            "port.hostPort <= variables.params.max))",
            '"container <" + container.name + "> has a hostPort outside '
            'the allowed range"')],
        "validations": [
            {"expression":
                 f"!(has({SPEC}.hostNetwork) && {SPEC}.hostNetwork == true)"
                 " || (has(variables.params.hostNetwork) && "
                 "variables.params.hostNetwork == true)",
             "messageExpression":
                 '"The specified hostNetwork is not allowed, pod: " + '
                 f"{META}.name"},
        ] + BAD_VALIDATION,
    }),
    "httpsonly": ("general", {
        "variables": [
            ("annotationComplete",
             f"has({META}.annotations) && "
             f'"{_ALLOW_HTTP}" in {META}.annotations && '
             f'{META}.annotations["{_ALLOW_HTTP}"] == "false"'),
            ("tlsOptional",
             "has(variables.params.tlsOptional) && "
             "variables.params.tlsOptional == true"),
        ],
        "validations": [{
            "expression":
                "variables.annotationComplete && (variables.tlsOptional || "
                f"(has({SPEC}.tls) && size({SPEC}.tls) > 0))",
            "messageExpression":
                f'"ingress <" + {META}.name + "> must be https: spec.tls '
                f'required unless tlsOptional, and the {_ALLOW_HTTP} '
                'annotation must be \\"false\\""',
        }],
    }),
    "imagedigests": ("general", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            '!container.image.matches("@sha256:[a-f0-9]{64}$")',
            '"container <" + container.name + "> uses an image without a '
            'digest <" + container.image + ">"')],
        "validations": BAD_VALIDATION,
    }),
    "readonlyrootfilesystem": ("general", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            f"!({_HAS_SC} && has({_SC}.readOnlyRootFilesystem) && "
            f"{_SC}.readOnlyRootFilesystem == true)",
            '"container <" + container.name + "> must set '
            'securityContext.readOnlyRootFilesystem to true"')],
        "validations": BAD_VALIDATION,
    }),
    "replicalimits": ("general", {
        "validations": [{
            "expression":
                f"!has({SPEC}.replicas) || "
                "variables.params.ranges.exists(r, "
                f"r.min_replicas <= {SPEC}.replicas && "
                f"{SPEC}.replicas <= r.max_replicas)",
            "messageExpression":
                '"The provided number of replicas is not allowed for " + '
                f'{OBJ}.kind + ": " + {META}.name',
        }],
    }),
    "requiredannotations": ("general",
                            _required("annotations", "an annotation")),
    "requiredlabels": ("general", _required("labels", "a label")),
    "requiredprobes": ("general", {
        "variables": [CONTAINERS, _bad(
            "variables.containers",
            "variables.params.probes.exists(probe, !(probe in container))",
            '"Container <" + container.name + "> in your Pod <" + '
            f'{META}.name + "> has no required probe"')],
        "validations": BAD_VALIDATION,
    }),
    "allowprivilegeescalation": ("pod-security-policy", {
        "variables": [
            CONTAINERS, INIT, _param_list("exempt", "exemptImages"),
            _bad(TWO,
                 "!variables.exempt.exists(e, container.image.startsWith(e))"
                 f" && !({_HAS_SC} && has({_SC}.allowPrivilegeEscalation) "
                 f"&& {_SC}.allowPrivilegeEscalation == false)",
                 '"Privilege escalation container is not allowed: " + '
                 "container.name")],
        "validations": BAD_VALIDATION,
    }),
    "apparmor": ("pod-security-policy", {
        "variables": [_param_list("allowedProfiles", "allowedProfiles")],
        "validations": [{
            "expression":
                f"!has({META}.annotations) || "
                f"{META}.annotations.all(key, profile, "
                f'!key.startsWith("{_APPARMOR}") || '
                "profile in variables.allowedProfiles)",
            "message": "AppArmor profile is not allowed",
        }],
    }),
    "flexvolumes": ("pod-security-policy", {
        "variables": [
            VOLUMES,
            _param_list("allowedFlexVolumes", "allowedFlexVolumes"),
            ("badFlexVolumes",
             "variables.volumes.filter(volume, has(volume.flexVolume) && "
             "has(volume.flexVolume.driver) && "
             "!variables.allowedFlexVolumes.exists(allowed, "
             "allowed.driver == volume.flexVolume.driver))"
             '.map(volume, "FlexVolume driver <" + '
             'volume.flexVolume.driver + "> is not allowed")'),
        ],
        "validations": [{
            "expression": "size(variables.badFlexVolumes) == 0",
            "messageExpression": 'variables.badFlexVolumes.join("\\n")',
        }],
    }),
    "fsgroup": ("pod-security-policy", {
        "variables": [
            ("mustRunAs",
             "has(variables.params.rule) && "
             'variables.params.rule == "MustRunAs"'),
            ("mayRunAs",
             "has(variables.params.rule) && "
             'variables.params.rule == "MayRunAs"'),
        ],
        "validations": [{
            "expression":
                f"({_HAS_POD_SC} && has({_POD_SC}.fsGroup)) ? "
                "(!(variables.mustRunAs || variables.mayRunAs) || "
                + _in_ranges(_POD_SC + ".fsGroup", "variables.params.ranges")
                + ") : !variables.mustRunAs",
            "message": "fsGroup must be specified under MustRunAs, and "
                       "lie in the allowed ranges",
        }],
    }),
    "procmount": ("pod-security-policy", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            "!(has(variables.params.procMount) && "
            'variables.params.procMount == "Unmasked") && '
            f"{_HAS_SC} && has({_SC}.procMount) && "
            f'{_SC}.procMount == "Unmasked"',
            '"ProcMount type is not allowed, container: " + container.name '
            '+ ". Allowed procMount types: Default"')],
        "validations": BAD_VALIDATION,
    }),
    "seccomp": ("pod-security-policy", {
        "variables": [
            CONTAINERS, INIT,
            _param_list("allowedProfiles", "allowedProfiles"),
            ("podHasProfile",
             f"{_HAS_POD_SC} && has({_POD_SECCOMP}) && "
             f"has({_POD_SECCOMP}.type)"),
            _bad(TWO,
                 '!("*" in variables.allowedProfiles) && '
                 f"(({_HAS_SC} && has({_SECCOMP}) && has({_SECCOMP}.type)) "
                 f"? !({_SECCOMP}.type in variables.allowedProfiles) : "
                 "(variables.podHasProfile ? "
                 f"!({_POD_SECCOMP}.type in variables.allowedProfiles) : "
                 "true))",
                 '"Seccomp profile is not allowed or not configured for '
                 'container <" + container.name + ">"')],
        "validations": BAD_VALIDATION,
    }),
    "selinux": ("pod-security-policy", {
        "variables": [CONTAINERS, INIT, _bad(
            TWO,
            f"{_HAS_SC} && has({_SC}.seLinuxOptions) && "
            "!variables.params.allowedSELinuxOptions.exists(a, "
            + _SELINUX.format(_SC) + ")",
            '"SELinux options are not allowed for container <" + '
            'container.name + ">"')],
        "validations": BAD_VALIDATION + [{
            "expression":
                f"!{_HAS_POD_SC} || !has({_POD_SC}.seLinuxOptions) || "
                "variables.params.allowedSELinuxOptions.exists(a, "
                + _SELINUX.format(_POD_SC) + ")",
            "message": "SELinux options are not allowed at pod level",
        }],
    }),
    "users": ("pod-security-policy", {
        "variables": [
            CONTAINERS, INIT,
            ("rule",
             "has(variables.params.runAsUser) && "
             "has(variables.params.runAsUser.rule) ? "
             'variables.params.runAsUser.rule : ""'),
            ("podHasUser", f"{_HAS_POD_SC} && has({_POD_USER})"),
            _bad(TWO,
                 '(variables.rule == "MustRunAs" && '
                 f"(({_HAS_SC} && has({_USER})) ? "
                 f"!{_in_ranges(_USER, _USER_RANGES)} : "
                 "(variables.podHasUser ? "
                 f"!{_in_ranges(_POD_USER, _USER_RANGES)} : true))) || "
                 '(variables.rule == "MustRunAsNonRoot" && '
                 f"{_HAS_SC} && has({_USER}) && {_USER} == 0)",
                 '"Container <" + container.name + "> is attempting to run '
                 'as a disallowed user, or without a required runAsUser"')],
        "validations": BAD_VALIDATION,
    }),
    "volumes": ("pod-security-policy", {
        "variables": [
            VOLUMES, _param_list("allowedTypes", "volumes"),
            ("badVolumes",
             "variables.volumes.filter(volume, "
             '!("*" in variables.allowedTypes) && '
             '!volume.all(field, field == "name" || '
             "field in variables.allowedTypes))"
             '.map(volume, "The volume type of volume <" + volume.name + '
             '"> is not allowed")'),
        ],
        "validations": [{
            "expression": "size(variables.badVolumes) == 0",
            "messageExpression": 'variables.badVolumes.join("\\n")',
        }],
    }),
}


def template(name: str) -> dict:
    """The stock template of ``name`` with its Rego replaced by the block."""
    area, source = POLICIES[name]
    with open(os.path.join(LIBRARY, area, name, "template.yaml")) as f:
        doc = yaml.safe_load(f)
    target = doc["spec"]["targets"][0]
    if "rego" not in target or "code" in target:
        raise ValueError(f"{area}/{name}: expected a Rego-only template")
    code = {}
    if source.get("variables"):
        code["variables"] = [{"name": n, "expression": e}
                             for n, e in source["variables"]]
    code["validations"] = [dict(v) for v in source["validations"]]
    code["failurePolicy"] = "Fail"
    doc["spec"]["targets"] = [{
        "target": target["target"],
        "code": [{"engine": "K8sNativeValidation", "source": code}],
    }]
    return doc


def write(out: str) -> int:
    """Write the library under ``out`` anew; returns the templates written."""
    if os.path.isdir(out):
        shutil.rmtree(out)
    for name in sorted(POLICIES):
        area = POLICIES[name][0]
        d = os.path.join(out, name)
        os.makedirs(os.path.join(d, "samples"))
        with open(os.path.join(d, "template.yaml"), "w") as f:
            yaml.safe_dump(template(name), f, sort_keys=False, width=78)
        shutil.copyfile(
            os.path.join(LIBRARY, area, name, "samples", "constraint.yaml"),
            os.path.join(d, "samples", "constraint.yaml"))
    return len(POLICIES)


def config_templates() -> list:
    """``library.templates`` of the configuration: the 36 here, then the two
    stock CEL templates and the eight kept on Rego, by their stock paths."""
    return ([f"../benchmark/libraries/cel/{n}" for n in sorted(POLICIES)]
            + CEL_STOCK + sorted(REGO_KEPT))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "cel"))
    args = ap.parse_args()
    print(f"{write(args.out)} templates -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
