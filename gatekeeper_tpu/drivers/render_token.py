"""What an exact-engine render of one constraint reads besides the review.

The audit's render memo (``audit/render_memo.py``) returns last pass's
messages for a (constraint, object) pair only if nothing the render read
has changed.  The review is the caller's to key (the object's bytes and
its ``source``); everything else a render reads is the driver's, and
``TpuDriver.render_token`` folds it into one hashable token:

* the template's compiled modules (``add_template`` / ``remove_template``
  / a generation swap install other objects),
* the ``Constraint`` object (``Client.add_constraint`` builds a new one on
  every add or update; its ``parameters`` and ``raw`` ride with it),
* the data document's epoch, for a template whose modules read ``data.``
  outside the template's own packages (``data.inventory``).

A CEL template's token is its compiled template and the Constraint: the
CEL evaluator binds nothing but the review and the parameters.

A token holds references to the objects it stands for and compares them
by identity, so a recycled ``id()`` can never make a stale hit.  A
template whose modules call a builtin with an effect or an input outside
the interpreter (``external_data``, ``print``) has no token: its renders
are never memoized.
"""

from __future__ import annotations

from dataclasses import fields, is_dataclass

from gatekeeper_tpu.lang.rego import ast
from gatekeeper_tpu.lang.rego.parser import WithWrapped

# builtins whose answer or effect lies outside (modules, input, data):
# the provider behind external_data, the hook print() delivers to
_IMPURE = frozenset({"external_data", "print"})


class RenderToken:
    __slots__ = ("template", "constraint", "data_epoch", "_hash")

    def __init__(self, template, constraint, data_epoch: int):
        self.template = template
        self.constraint = constraint
        self.data_epoch = data_epoch
        self._hash = hash((id(template), id(constraint), data_epoch))

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return (type(other) is RenderToken
                and other.template is self.template
                and other.constraint is self.constraint
                and other.data_epoch == self.data_epoch)


def _own_package(by_pkg: dict, path) -> bool:
    """True when a ``data.<path>`` reference lands in or under one of the
    template's own packages: the interpreter resolves it to that module's
    rules and never reaches the data store."""
    return any(tuple(path[:n]) in by_pkg for n in range(1, len(path) + 1))


def module_reads(modules) -> tuple:
    """(pure, reads_data) of a compiled ``ModuleSet``, by one walk of its
    AST.  ``pure``: no call of an impure builtin.  ``reads_data``: some
    reference to ``data`` that is not provably inside the template's own
    packages (a constant path under one of them); a dynamic or partial
    path counts as a read, so the answer errs toward the data epoch."""
    by_pkg = modules.by_pkg
    pure, reads_data = True, False
    stack: list = []
    for mod in by_pkg.values():
        for path in mod.imports.values():
            if path and path[0] == "data" and \
                    not _own_package(by_pkg, path[1:]):
                reads_data = True
        stack.extend(mod.rules.values())
    while stack:
        x = stack.pop()
        if isinstance(x, (tuple, list)):
            stack.extend(x)
        elif isinstance(x, WithWrapped):
            stack.append(x.stmt)
            for target, term in x.withs:
                if target[0] == "data":
                    reads_data = True
                stack.append(term)
        elif isinstance(x, ast.Ref) and x.head == ast.Var("data"):
            const = []
            for a in x.args:
                if not (isinstance(a, ast.Scalar)
                        and isinstance(a.value, str)):
                    break
                const.append(a.value)
            if not _own_package(by_pkg, const):
                reads_data = True
            stack.extend(x.args)
        elif isinstance(x, ast.Var):
            if x.name == "data":
                reads_data = True
        elif is_dataclass(x):
            if isinstance(x, ast.Call) and x.op in _IMPURE:
                pure = False
            stack.extend(getattr(x, f.name) for f in fields(x))
    return pure, reads_data
