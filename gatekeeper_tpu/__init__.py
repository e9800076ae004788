"""gatekeeper_tpu: a TPU-native policy-enforcement framework.

A ground-up re-design of the capabilities of open-policy-agent/gatekeeper
(reference: /root/reference) for TPU hardware:

- ConstraintTemplates (Rego / CEL source) are parsed and *partial-evaluated* at
  AddTemplate time and, where the policy falls in the vectorizable subset,
  lowered to a columnar predicate program executed as one batched JAX/XLA
  kernel (``vmap`` over an object batch x constraint axis).  Policies outside
  the subset fall back to an exact logic interpreter behind the same
  ``Driver.Query`` seam, so verdicts are always available and always exact.
- Constraint ``spec.match`` rules (kinds, namespaces, selectors, ...) become
  boolean masks over the flattened object batch (reference semantics:
  pkg/mutation/match/match.go).
- The audit sweep shards the object batch over a ``jax.sharding.Mesh``
  (data-parallel over chips via ICI, hosts via DCN) with a per-constraint
  device top-k reduction mirroring the reference's LimitQueue
  (pkg/audit/manager.go:161).

Layer map (mirrors SURVEY.md section 1):

==========  ==========================================================
L0          ``gatekeeper_tpu.drivers``       policy engines (tpu / rego / cel)
L1          ``gatekeeper_tpu.client``        constraint-framework client
L2          ``gatekeeper_tpu.target``        target handler + match
L3          ``gatekeeper_tpu.webhook``       admission webhooks
L4          ``gatekeeper_tpu.audit``         audit sweep
L5          ``gatekeeper_tpu.mutation`` / ``.expansion``
L6          ``gatekeeper_tpu.gator``         offline CLI
L7          ``gatekeeper_tpu.sync``          data-sync plane (inventory)
L9          ``gatekeeper_tpu.readiness``
L10         ``gatekeeper_tpu.metrics`` / ``.export``
==========  ==========================================================
"""

__version__ = "0.1.0"

