"""CEL (K8sNativeValidation) → predicate-IR lowering.

The reference evaluates CEL templates with a per-(constraint, review)
cel-go program loop (pkg/drivers/k8scel/driver.go:162-251).  Here the same
vectorizable fragment that ir/lower_rego.py covers for Rego lowers CEL
validations onto the SAME device IR (ir/nodes.py), so CEL constraints join
the fused [C, N] verdict sweep instead of running a per-object Python
evaluator.

Exact semantics being lowered (drivers/cel_driver.py query loop):
a validation VIOLATES iff its expression does NOT evaluate to exactly
``true`` — evaluating to false, to a non-bool, or erroring (under
``failurePolicy: Fail``) all violate.  The lowerer therefore tracks DUAL
polarity for every boolean subexpression:

    t(E): device expr that is true  iff E evaluates to exactly true
    f(E): device expr that is true  iff E evaluates to exactly false

and the violation expression is ``Not(t(E))`` — which correctly includes
CEL's error outcomes because every primitive's t/f forms are definedness-
gated (absent fields, non-string operands to string predicates, and
unparseable quantities make both polarities false).

CEL's error-absorbing && / || map exactly onto this dual form:
    t(a && b) = t(a) ∧ t(b)        f(a && b) = f(a) ∨ f(b)
    t(a || b) = t(a) ∨ t(b)        f(a || b) = f(a) ∧ f(b)
    t(!a) = f(a)                   f(!a) = t(a)
macros:
    t(L.all(x, P))    = ¬∃item ¬t(P)      f = ∃item f(P)
    t(L.exists(x, P)) = ∃item t(P)        f = ¬∃item ¬f(P)
    t(size(L.filter(x, P)) == 0) = ¬∃item ¬f(P)   (all items exactly false)
    t(size(L.filter(x, P).map(x, B)) == 0) the same: B runs on kept items
      only; f needs B defined on every kept item

What lowers (the gatekeeper-library idiom, benchmark/libraries/cel):
- object lists: paths, ``a + b`` concatenation, the ``has(p) ? p : []``
  guard idiom (nested guards too); macros all/exists/exists_one over them,
  over maps (keys, or key and value in the two-variable form) and, one level
  down, over a list or map under the macro's item (``c.ports.all(p, ...)``,
  ``c.securityContext.capabilities.add``, ``volume.all(field, ...)``), as a
  per-item NestedAny;
- ``.filter(...)`` and ``.filter(...).map(...)`` in value position where
  ``size(...)`` against 0 reads the result, over object lists and over
  parameter lists; ``x.f in L.filter(v, Q).map(v, v.f)`` where x ranges over
  the same L and Q reads v through ``v.f`` alone (the exemptImages idiom);
- ``in`` over parameter lists, list literals, object lists and maps (keys),
  also under the item; ``map[key]`` with a parameter key as the subject of a
  string method;
- parameters at nested paths (``params.runAsUser.rule``), lists of parameter
  objects (``params.ranges.exists(r, r.min <= x)``), numeric comparison of an
  object field with a parameter;
- everything that reads the parameters alone (``":" + tag``,
  ``string(image).replace("*", "")``, a filter or map of a parameter list, a
  ternary of parameters) is not lowered at all: the CEL evaluator computes it
  on the host once per constraint when the parameter table is built
  (``CelDerive``, ``CelDeriveElems``), errors included, and the device reads
  the value as one more parameter;
- comparisons on quantities (isQuantity/quantity().isGreaterThan/...),
  booleans, strings and numbers; string methods startsWith/endsWith/
  contains/matches;
- ``request.operation`` (``__review__.operation``: "" in an audit review).

Fragment boundaries (anything else raises LowerError → interpreter
fallback behind the same Driver seam):
- failurePolicy must be Fail (Ignore absorbs errors differently);
- no matchConditions;
- no oldObject / namespaceObject, nothing of ``request`` but ``operation``;
- no conversion (``string(x)``, ``int(x)``), arithmetic or concatenation on
  the object's side: the device has no string it did not see;
- two levels of nesting under a macro item; a concatenation of lists under
  an item; exists_one under an item;
- parameters are taken to have the types their CRD schema gives them.

Messages are NOT lowered: hits render through the CEL evaluator
(messageExpression semantics preserved).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Optional

from gatekeeper_tpu.ir import nodes as N
from gatekeeper_tpu.ir.program import LowerError, _ElemListSid
from gatekeeper_tpu.lang.cel import cel as C
from gatekeeper_tpu.ops.flatten import (Axis, K_FALSE, K_MAP, K_NUM, K_OTHER,
                                        K_STR, K_TRUE, MapKeyCol,
                                        ParentIdxCol, RaggedCol, ScalarCol,
                                        Schema)

QUANTITY_FN = "cel.quantity"
REVIEW_ROOT = "__review__"

_STR_METHODS = {"startsWith": "startswith", "endsWith": "endswith",
                "contains": "contains", "matches": "re_match"}
_QTY_CMP = {"isGreaterThan": ("gt", "lte"), "isLessThan": ("lt", "gte")}


# --- symbolic values ------------------------------------------------------


class SVal:
    __slots__ = ()


@dataclass(frozen=True)
class SObj(SVal):
    """Value at a path under the review object root (or, under
    ``__review__``, of the review itself)."""

    path: tuple


@dataclass(frozen=True)
class SItem(SVal):
    """Field of the current macro item on a ragged axis."""

    axis: Axis
    subpath: tuple


@dataclass(frozen=True)
class ListPart(SVal):
    """One source of a (possibly concatenated) list value.

    ``empty_guards``: exprs under which the source evaluates to a DEFINED
    empty list via the ``!has(p) ? [] : p`` idiom (each is the exactly-
    false form of the corresponding has()).  ``path`` locates the value
    for list/map kind gating: under the object root, or under the item of
    ``parent`` for a list one level down."""

    path: tuple
    empty_guards: tuple = ()
    parent: Optional[Axis] = None


@dataclass(frozen=True)
class SList(SVal):
    """A list value backed by a ragged axis over one or more parts.

    CEL outcome model per part: ERROR (base chain broken / unguarded
    absent / non-list value), EMPTY (a guard fired), LIST (items).  Maps
    are NOT lists: a macro over a non-empty map iterates KEYS (the axis's
    MapKeyColumn) and a concat over a map errors.  ``parent``: the axis
    of the macro item this list lives under (reductions are per item)."""

    axis: Axis
    parts: tuple  # tuple[ListPart]
    parent: Optional[Axis] = None


@dataclass(frozen=True)
class SFiltered(SVal):
    """``L.filter(var, body)`` — lowered lazily at the size() comparison."""

    source: Any  # SList | SParamList
    var: str
    body: Any
    env: tuple  # frozen env items


@dataclass(frozen=True)
class SMapped(SVal):
    """``L.map(var, body)`` over a list or a filtered list — lowered lazily
    where its size or a membership reads it."""

    source: Any  # SList | SParamList | SFiltered
    var: str
    body: Any
    env: tuple


@dataclass(frozen=True)
class SParam(SVal):
    path: tuple  # under params root


@dataclass(frozen=True, eq=False)
class SParamList(SVal):
    """A list parameter.  ``guarded``: an absent parameter is a defined []
    (the has() idiom); else absence is CEL's error.  ``src``: the CEL
    expression that yields the list, for the host-derived fallback."""

    name: str
    guarded: bool = False
    src: Any = None


@dataclass(frozen=True)
class SParamElem(SVal):
    name: str


@dataclass(frozen=True)
class SParamElemField(SVal):
    name: str
    field: tuple


@dataclass(frozen=True)
class SDerivedElem(SVal):
    """The element variable of a parameter-list macro whose body is lowered
    over a host-derived list of objects: every expression of the element
    and the parameters alone becomes one field of it."""

    name: str  # the derived objlist parameter
    var: str


@dataclass(frozen=True)
class SMapKey(SVal):
    """The current macro item's MAP KEY: CEL macros over maps iterate
    keys, and the flattener's ragged axes carry an aligned MapKeyColumn
    (sid per value item, -1 for list-backed items) — so a key-predicate
    body lowers to string ops over that column."""

    axis: Axis


@dataclass(frozen=True)
class SIndex(SVal):
    """The int index a two-variable macro binds over a LIST: every string
    method on it errors."""


@dataclass(frozen=True)
class SMapLookup(SVal):
    """``map[key]`` with a parameter-side key: the value item of the map's
    axis whose key equals it."""

    path: tuple
    key: SVal


@dataclass(frozen=True)
class SLit(SVal):
    value: Any


@dataclass(frozen=True)
class SQuantity(SVal):
    arg: SVal


class _VariablesMarker(SVal):
    __slots__ = ()


class _RequestMarker(SVal):
    __slots__ = ()


@dataclass(frozen=True)
class _Poison(N.Expr):
    """A polarity this lowerer cannot state.  Harmless while nothing reads
    it (a validation reads only the exactly-true form); a program that
    still holds one at the end does not lower."""

    reason: str


# --- host-derived parameters ----------------------------------------------

def _derive_env(variables: tuple, params) -> C.Env:
    lazy = dict(variables)
    lazy["params"] = C.Ident("params")
    return C.Env({"params": params}, lazy)


@dataclass(frozen=True, eq=False)
class CelDerive:
    """A parameter the CEL evaluator computes on the host, once per
    constraint, from the constraint's parameters alone."""

    ast: Any
    variables: tuple  # ((name, ast), ...) — the template's variables

    def value(self, params) -> tuple:
        """(ok, value): not ok where the expression errors."""
        try:
            return True, C.evaluate(self.ast,
                                    _derive_env(self.variables, params))
        except C.CelError:
            return False, None


@dataclass(eq=False)
class CelDeriveElems:
    """A list parameter derived element by element: for each element of
    ``ast``'s list, an object whose fields are the values of ``fields``'
    expressions with ``var`` bound to it.  A field whose expression errors
    is left out of that element's object — the error is that element's
    alone, as in CEL's macros."""

    ast: Any
    var: str
    fields: list  # [(name, ast)]
    variables: tuple

    def value(self, params) -> tuple:
        env = _derive_env(self.variables, params)
        try:
            items = C.evaluate(self.ast, env)
        except C.CelError:
            return False, None
        if not isinstance(items, list):
            return False, None
        out = []
        for x in items:
            sub = env.child(self.var, x)
            obj = {}
            for name, ast in self.fields:
                try:
                    v = C.evaluate(ast, sub)
                except C.CelError:
                    continue
                obj[name] = int(v) if isinstance(v, bool) else v
            out.append(obj)
        return True, out


def _check_no_bare_var(ast, var: str) -> None:
    """CEL macros iterate map KEYS; the ragged axis holds VALUES.  The
    _list_ok gates emit the ERROR outcome for macros over non-empty maps,
    which is exact only if the body genuinely errors on every string key.
    Three conditions enforce that statically:

    - the variable is never used BARE (a value use like ``k == "x"`` is
      key-sensitive and evaluates fine on strings);
    - BOTH outcomes of the body require a successful dereference of the
      variable (CEL's absorbing && / || can otherwise decide the body
      without touching the var: ``has(c.x) || true`` is TRUE over keys,
      ``has(c.x) && false`` is FALSE over keys — either would diverge)."""
    t_req, f_req = _deref_req(ast, var)
    if not (t_req and f_req):
        raise LowerError(
            f"macro body can decide without dereferencing {var}")


def _deref_req(ast, var: str) -> tuple:
    """(t_req, f_req): whether the body's exactly-true / exactly-false
    outcome entails a successful deref of ``var`` (vacuous outcomes count
    as requiring).  Raises on bare uses."""
    if isinstance(ast, C.Lit):
        if ast.value is True:
            return False, True
        if ast.value is False:
            return True, False
        return True, True  # non-bool literal can't decide a bool body
    if isinstance(ast, C.Unary) and ast.op == "!":
        t, f = _deref_req(ast.operand, var)
        return f, t
    if isinstance(ast, C.Binary) and ast.op in ("&&", "||"):
        lt, lf = _deref_req(ast.lhs, var)
        rt, rf = _deref_req(ast.rhs, var)
        if ast.op == "&&":
            return (lt or rt), (lf and rf)
        return (lt and rt), (lf or rf)
    if isinstance(ast, C.Ternary):
        ct, cf = _deref_req(ast.cond, var)
        at, af = _deref_req(ast.then, var)
        bt, bf = _deref_req(ast.other, var)
        return ((ct or at) and (cf or bt)), ((ct or af) and (cf or bf))
    if isinstance(ast, C.Macro):
        # nested macro (e.g. over a param list): true needs a true element
        # (body true), false is reachable with an empty source (no deref)
        bt, bf = _deref_req(ast.body, var)
        tgt = _count_var_derefs(ast.target, var, False) > 0
        if ast.name == "exists":
            return (tgt or bt), tgt
        if ast.name == "all":
            return tgt, (tgt or bf)
        return False, False  # filter/map: analyzed at their comparison
    # leaf predicate (comparison, method, has, in): both outcomes imply its
    # operands evaluated — derefs under nested macro BODIES don't count
    # (an empty source decides without evaluating the body)
    d = _count_var_derefs(ast, var, False, skip_macro_bodies=True) > 0
    return d, d


def _count_var_derefs(ast, var: str, safe: bool,
                      skip_macro_bodies: bool = False) -> int:
    count = 0
    if isinstance(ast, C.Ident):
        if ast.name == var:
            if not safe:
                raise LowerError(f"macro variable {var} used bare")
            return 1
        return 0
    if isinstance(ast, C.Select):
        return _count_var_derefs(ast.base, var, True, skip_macro_bodies)
    if isinstance(ast, C.Index):
        return (_count_var_derefs(ast.base, var, True, skip_macro_bodies)
                + _count_var_derefs(ast.index, var, False,
                                    skip_macro_bodies))
    if isinstance(ast, C.Call):
        # only Select/Index BASE positions deref; a method target or call
        # argument uses the value itself (string ops on a map key work)
        if ast.target is not None:
            count += _count_var_derefs(ast.target, var, False,
                                       skip_macro_bodies)
        for a in ast.args:
            count += _count_var_derefs(a, var, False, skip_macro_bodies)
        return count
    if isinstance(ast, C.Macro) and skip_macro_bodies:
        return _count_var_derefs(ast.target, var, False, skip_macro_bodies)
    for f in getattr(ast, "__dataclass_fields__", {}):
        v = getattr(ast, f)
        if isinstance(v, (C.Lit, C.Ident, C.Select, C.Index, C.Call,
                          C.Unary, C.Binary, C.Ternary, C.ListLit,
                          C.MapLit, C.Macro)):
            count += _count_var_derefs(v, var, False, skip_macro_bodies)
        elif isinstance(v, tuple):
            count += sum(_count_var_derefs(item, var, False,
                                           skip_macro_bodies)
                         for item in v)
    return count


_VARIABLES = _VariablesMarker()
_REQUEST = _RequestMarker()
_TRUE = N.ConstBool(True)
_FALSE = N.ConstBool(False)


def _and(*terms):
    flat = [t for t in terms if t is not _TRUE]
    if any(t is _FALSE for t in flat):
        return _FALSE
    if not flat:
        return _TRUE
    return flat[0] if len(flat) == 1 else N.And(tuple(flat))


def _or(*terms):
    flat = [t for t in terms if t is not _FALSE]
    if any(t is _TRUE for t in flat):
        return _TRUE
    if not flat:
        return _FALSE
    return flat[0] if len(flat) == 1 else N.Or(tuple(flat))


def _not(term):
    if term is _TRUE:
        return _FALSE
    if term is _FALSE:
        return _TRUE
    return N.Not(term)


def _is_call(ast, name: str, nargs: int = 1) -> bool:
    return (isinstance(ast, C.Call) and ast.target is None
            and ast.name == name and len(ast.args) == nargs)


def _reads_only_field(ast, var: str, field: str) -> bool:
    """Every occurrence of ``var`` in ``ast`` is ``var.<field>``."""
    if isinstance(ast, C.Select) and isinstance(ast.base, C.Ident) \
            and ast.base.name == var:
        return ast.field == field
    if isinstance(ast, C.Ident):
        return ast.name != var
    if isinstance(ast, C.Macro) and var in (ast.var, ast.var2):
        return _reads_only_field(ast.target, var, field)  # shadowed below
    for f in getattr(ast, "__dataclass_fields__", {}):
        v = getattr(ast, f)
        if isinstance(v, tuple):
            if not all(_reads_only_field(x, var, field) for x in v
                       if not isinstance(x, (str, int, float, bool,
                                             type(None)))):
                return False
        elif hasattr(v, "__dataclass_fields__"):
            if not _reads_only_field(v, var, field):
                return False
    return True


class _CelLowerer:
    def __init__(self, variables: dict, vocab, schema_hint: Optional[dict]):
        self.variables = variables  # name -> CEL AST
        self.vocab = vocab
        self.schema = Schema()
        self.param_kinds: dict[str, str] = {}
        self.param_fields: dict[str, dict] = {}  # objlist name -> field->type
        self.weak_params: set = set()  # has()-only params (type unclaimed)
        self.derived: dict[str, Any] = {}  # name -> CelDerive(Elems)
        self._var_stack: list[str] = []
        self._axis_stack: list = []  # the axis whose item is in scope
        self._nested: set = set()  # child axes (one level under an item)
        self._roots_memo: dict[str, frozenset] = {}
        self._frozen_variables = tuple(variables.items())

    # --- failed-attempt bookkeeping -----------------------------------
    def _snapshot(self):
        s = self.schema
        return (dict(self.param_kinds),
                {k: dict(v) for k, v in self.param_fields.items()},
                set(self.weak_params), dict(self.derived),
                {k: list(v.fields) for k, v in self.derived.items()
                 if isinstance(v, CelDeriveElems)},
                [len(x) for x in (s.scalars, s.raggeds, s.keysets,
                                  s.ragged_keysets, s.map_keys, s.parent_idx,
                                  s.canons, s.extra_axes)],
                set(self._nested), list(self._axis_stack),
                list(self._var_stack))

    def _restore(self, snap):
        (self.param_kinds, self.param_fields, self.weak_params,
         self.derived, fields, lens, self._nested, self._axis_stack,
         self._var_stack) = snap
        for k, v in fields.items():
            self.derived[k].fields[:] = v
        s = self.schema
        for lst, n in zip((s.scalars, s.raggeds, s.keysets,
                           s.ragged_keysets, s.map_keys, s.parent_idx,
                           s.canons, s.extra_axes), lens):
            del lst[n:]

    # --- schema/column helpers ---------------------------------------
    def _scalar_col(self, path: tuple) -> ScalarCol:
        col = ScalarCol(path=path)
        if col not in self.schema.scalars:
            self.schema.scalars.append(col)
        return col

    def _in_scope(self, axis: Axis):
        if self._axis_stack and self._axis_stack[-1] != axis:
            raise LowerError("an outer macro's item read under an inner one")

    def _ragged_col(self, axis: Axis, subpath: tuple) -> RaggedCol:
        self._in_scope(axis)
        col = RaggedCol(axis=axis, subpath=subpath)
        if col not in self.schema.raggeds:
            self.schema.raggeds.append(col)
        return col

    def _feat_col(self, sv: SVal):
        if isinstance(sv, SObj):
            return self._scalar_col(sv.path)
        if isinstance(sv, SItem):
            return self._ragged_col(sv.axis, sv.subpath)
        raise LowerError(f"no column for {sv}")

    def _note_param(self, name: str, kind: str):
        prev = self.param_kinds.get(name)
        if prev is not None and prev != kind:
            raise LowerError(f"param {name} used as {prev} and {kind}")
        self.param_kinds[name] = kind

    def _note_field(self, name: str, field: tuple, ftype: str):
        self._note_param(name, "objlist")
        fields = self.param_fields.setdefault(name, {})
        prev = fields.get(field)
        if prev is not None and prev != ftype:
            raise LowerError(f"param {name}.{'.'.join(field)} used as "
                             f"{prev} and {ftype}")
        fields[field] = ftype

    @staticmethod
    def _pname(path: tuple) -> str:
        """A parameter's name in the table: its dotted path (program.p_get
        walks it where no literal key matches)."""
        if not path:
            raise LowerError("the params root as a value")
        return ".".join(path)

    # --- what an expression reads -------------------------------------
    def _roots(self, ast, bound: frozenset = frozenset()) -> frozenset:
        """The roots an expression reads: "params", "object" (the review
        side) and ("var", name) for every free identifier."""
        if isinstance(ast, (C.Lit, str, int, float, bool, type(None))):
            return frozenset()
        if isinstance(ast, C.Ident):
            if ast.name in bound:
                return frozenset()
            if ast.name == "params":
                return frozenset({"params"})
            if ast.name in ("object", "oldObject", "request",
                            "namespaceObject", "anyObject", "authorizer"):
                return frozenset({"object"})
            return frozenset({("var", ast.name)})
        if isinstance(ast, C.Select) and isinstance(ast.base, C.Ident) \
                and ast.base.name == "variables" \
                and "variables" not in bound:
            return self._variable_roots(ast.field)
        if isinstance(ast, C.Macro):
            inner = bound | {ast.var} | ({ast.var2} if ast.var2 else set())
            out = self._roots(ast.target, bound) | \
                self._roots(ast.body, inner)
            if ast.body2 is not None:
                out |= self._roots(ast.body2, inner)
            return out
        out: frozenset = frozenset()
        for f in getattr(ast, "__dataclass_fields__", {}):
            v = getattr(ast, f)
            if isinstance(v, tuple):
                for x in v:
                    if isinstance(x, tuple):  # MapLit pairs
                        for y in x:
                            out |= self._roots(y, bound)
                    else:
                        out |= self._roots(x, bound)
            else:
                out |= self._roots(v, bound)
        return out

    def _variable_roots(self, name: str) -> frozenset:
        if name == "params":
            return frozenset({"params"})
        if name == "anyObject" or name not in self.variables:
            return frozenset({"object"})
        memo = self._roots_memo
        if name not in memo:
            memo[name] = frozenset({"object"})  # a cycle reads as object
            memo[name] = self._roots(self.variables[name])
        return memo[name]

    def _derivable(self, ast, env: dict) -> Optional[SDerivedElem]:
        """Whether the host can compute ``ast``: it reads the parameters
        alone (returns True), or them and the element of one derived
        parameter-list macro (returns that element), else None."""
        if isinstance(ast, C.Lit):
            return None
        elem = None
        for r in self._roots(ast):
            if r == "params":
                continue
            if r == "object":
                return None
            sv = env.get(r[1])
            if not isinstance(sv, SDerivedElem) or \
                    (elem is not None and sv != elem):
                return None
            elem = sv
        return elem or True

    def _derive(self, ast, env: dict) -> Optional[SVal]:
        """``ast`` as a host-derived parameter (or a field of the derived
        element), or None where the host cannot compute it."""
        how = self._derivable(ast, env)
        if how is None:
            return None
        if how is True:
            for name, d in self.derived.items():
                if isinstance(d, CelDerive) and d.ast == ast:
                    return SParam((name,))
            name = f"~{len(self.derived)}"
            self.derived[name] = CelDerive(ast, self._frozen_variables)
            return SParam((name,))
        d = self.derived[how.name]
        for fname, fast in d.fields:
            if fast == ast:
                return SParamElemField(how.name, (fname,))
        fname = f"e{len(d.fields)}"
        d.fields.append((fname, ast))
        return SParamElemField(how.name, (fname,))

    # --- operand builders --------------------------------------------
    def _sid(self, sv: SVal) -> N.Expr:
        """sid-valued operand (string reads)."""
        if isinstance(sv, (SObj, SItem)):
            return N.FeatSid(self._feat_col(sv))
        if isinstance(sv, SParam):
            name = self._pname(sv.path)
            self._note_param(name, "str")
            return N.ParamSid(name)
        if isinstance(sv, SParamElem):
            self._note_param(sv.name, "strlist")
            return N.ParamElemSid()
        if isinstance(sv, SParamElemField):
            self._note_field(sv.name, sv.field, "str")
            return N.ParamElemFieldSid(sv.name, sv.field)
        if isinstance(sv, SMapKey):
            return N.MapKeySid(self._map_key_col(sv.axis))
        if isinstance(sv, SLit) and isinstance(sv.value, str):
            return N.ConstSid(self.vocab.intern(sv.value))
        raise LowerError(f"not a string operand: {sv}")

    def _map_key_col(self, axis: Axis):
        self._in_scope(axis)
        col = MapKeyCol(axis=axis)
        if col not in self.schema.map_keys:
            self.schema.map_keys.append(col)
        return col

    def _is_str(self, sv: SVal) -> N.Expr:
        """Defined-string test for the false-polarity gates."""
        if isinstance(sv, (SObj, SItem)):
            return N.KindIs(self._feat_col(sv), K_STR)
        if isinstance(sv, SParam):
            name = self._pname(sv.path)
            self._note_param(name, "str")
            return N.ParamPresent(name)
        if isinstance(sv, SParamElemField):
            self._note_field(sv.name, sv.field, "str")
            return N.ParamElemFieldPresent(sv.name, sv.field)
        if isinstance(sv, (SParamElem, SLit, SMapKey)):
            return _TRUE  # map keys are always defined strings
        raise LowerError(f"not a string operand: {sv}")

    def _defined(self, sv: SVal) -> N.Expr:
        """The operand evaluates without error, any type (CEL equality is
        heterogeneous: mixed-type == is a defined false, not an error)."""
        if isinstance(sv, (SObj, SItem)):
            return N.Present(self._feat_col(sv))
        if isinstance(sv, SParam):
            name = self._pname(sv.path)
            self.weak_params.add(name)
            return N.ParamPresent(name)
        if isinstance(sv, SParamElemField):
            if sv.field not in self.param_fields.get(sv.name, {}):
                self._note_field(sv.name, sv.field, "str")
            return N.ParamElemFieldPresent(sv.name, sv.field)
        if isinstance(sv, (SParamElem, SLit, SMapKey)):
            return _TRUE
        raise LowerError(f"no definedness test for {sv}")

    def _has_pair(self, sv: SVal) -> tuple:
        """CEL has(a.b.c): true iff the leaf exists (walk implies the base
        chain was maps); exactly-FALSE requires every proper prefix to be a
        present map (a broken base chain ERRORS — has() is not total)."""
        if isinstance(sv, SObj):
            if not sv.path:
                raise LowerError("has() of the object root")
            if sv.path[0] == REVIEW_ROOT:
                # the review document always carries the field (""
                # outside admission): has() is exactly true
                return _TRUE, _FALSE
            t = N.Present(self._scalar_col(sv.path))
            gates = [
                N.KindIs(self._scalar_col(sv.path[:i]), K_MAP)
                for i in range(1, len(sv.path))
            ]
            return t, _and(*gates, N.Not(t))
        if isinstance(sv, SItem):
            if not sv.subpath:
                raise LowerError("has() of a bare loop variable")
            t = N.Present(self._ragged_col(sv.axis, sv.subpath))
            # from the item itself down: has(c.x) on a string c errors
            gates = [
                N.KindIs(self._ragged_col(sv.axis, sv.subpath[:i]), K_MAP)
                for i in range(len(sv.subpath))
            ]
            return t, _and(*gates, N.Not(t))
        if isinstance(sv, SParam):
            name = self._pname(sv.path)
            # kind noted at the USE site; has() alone doesn't fix a type —
            # weak 'str' default applied at build unless a use claims it
            self.weak_params.add(name)
            pres = N.ParamPresent(name)
            if len(sv.path) == 1:
                return pres, N.Not(pres)  # params root is always a map
            parent = self._pname(sv.path[:-1])
            self.weak_params.add(parent)
            return pres, _and(N.ParamPresent(parent), N.Not(pres))
        if isinstance(sv, SParamElemField):
            pres = self._defined(sv)
            if len(sv.field) != 1:
                raise LowerError("has() of a nested parameter-element field")
            return pres, N.Not(pres)  # the element is an object (schema)
        raise LowerError(f"has() of {sv}")

    def _num(self, sv: SVal) -> N.Expr:
        if isinstance(sv, SLit) and isinstance(sv.value, (int, float)) \
                and not isinstance(sv.value, bool):
            return N.ConstNum(float(sv.value))
        if isinstance(sv, SQuantity):
            arg = sv.arg
            if isinstance(arg, SParam):
                name = self._pname(arg.path)
                self._note_param(name, "str")
                return N.ParamFnNum(QUANTITY_FN, name)
            return N.StrFnNum(QUANTITY_FN, self._sid(arg))
        if isinstance(sv, (SObj, SItem)):
            return N.FeatNum(self._feat_col(sv))
        if isinstance(sv, SParam):
            name = self._pname(sv.path)
            self._note_param(name, "num")
            return N.ParamNum(name)
        if isinstance(sv, SParamElemField):
            self._note_field(sv.name, sv.field, "num")
            return N.ParamElemFieldNum(sv.name, sv.field)
        raise LowerError(f"not numeric: {sv}")

    def _num_gate(self, sv: SVal) -> N.Expr:
        """CEL errors on cross-type comparison (no Rego total order): gate
        feature reads on the numeric kind tag.  Parameters are numbers by
        their schema; an absent one fails CmpNum's presence test."""
        if isinstance(sv, (SObj, SItem)):
            return N.KindIs(self._feat_col(sv), K_NUM)
        return _TRUE  # literals always; quantities gate via validity

    # --- value lowering ----------------------------------------------
    def value(self, ast, env: dict) -> SVal:
        """Native lowering first; what reads the parameters alone and does
        not lower is computed on the host instead (``_derive``)."""
        if isinstance(ast, C.Macro) and ast.name in ("filter", "map"):
            d = self._derive(ast, env)  # a list the host can hold whole
            if d is not None:
                return d
        snap = self._snapshot()
        try:
            return self._value(ast, env)
        except LowerError:
            self._restore(snap)
            d = self._derive(ast, env)
            if d is None:
                raise
            return d

    def _value(self, ast, env: dict) -> SVal:
        if isinstance(ast, C.Lit):
            return SLit(ast.value)
        if isinstance(ast, C.Ident):
            name = ast.name
            if name in env:
                sv = env[name]
                if isinstance(sv, SDerivedElem):
                    return self._derive(ast, env)
                return sv
            if name == "variables":
                return _VARIABLES
            if name in ("object", "anyObject"):
                return SObj(())
            if name == "params":
                return SParam(())
            if name == "request":
                return _REQUEST
            if name in ("oldObject", "namespaceObject"):
                raise LowerError(f"unsupported root {name}")
            raise LowerError(f"unknown ident {name}")
        if isinstance(ast, C.Select):
            base = self.value(ast.base, env)
            if isinstance(base, _VariablesMarker):
                return self._resolve_variable(ast.field, env)
            if isinstance(base, _RequestMarker):
                if ast.field != "operation":
                    raise LowerError(f"unsupported request.{ast.field}")
                return SObj((REVIEW_ROOT, "operation"))
            if isinstance(base, SObj):
                if base.path[:1] == (REVIEW_ROOT,):
                    raise LowerError("select under request.operation")
                return SObj(base.path + (ast.field,))
            if isinstance(base, SItem):
                return SItem(base.axis, base.subpath + (ast.field,))
            if isinstance(base, SParam):
                return SParam(base.path + (ast.field,))
            if isinstance(base, SParamElem):
                return SParamElemField(base.name, (ast.field,))
            if isinstance(base, SParamElemField) and \
                    base.name not in self.derived:
                return SParamElemField(base.name, base.field + (ast.field,))
            raise LowerError(f"select .{ast.field} on {base}")
        if isinstance(ast, C.Index):
            base = self.value(ast.base, env)
            if isinstance(ast.index, C.Lit) and isinstance(
                    ast.index.value, str):
                if isinstance(base, SObj):
                    return SObj(base.path + (ast.index.value,))
                if isinstance(base, SItem):
                    return SItem(base.axis,
                                 base.subpath + (ast.index.value,))
                if isinstance(base, SParam):
                    return SParam(base.path + (ast.index.value,))
            if isinstance(base, SObj) and base.path[:1] != (REVIEW_ROOT,):
                key = self.value(ast.index, env)
                if isinstance(key, (SParam, SParamElem, SParamElemField)):
                    if self._axis_stack:
                        raise LowerError("map[key] under a macro item")
                    return SMapLookup(base.path, key)
            raise LowerError("dynamic index")
        if isinstance(ast, C.Call):
            if ast.target is None and ast.name == "quantity" \
                    and len(ast.args) == 1:
                return SQuantity(self.value(ast.args[0], env))
            raise LowerError(f"call {ast.name} in value position")
        if isinstance(ast, C.Binary) and ast.op == "+":
            lhs = self._as_list(self.value(ast.lhs, env))
            rhs = self._as_list(self.value(ast.rhs, env))
            if isinstance(lhs, SList) and isinstance(rhs, SList):
                if lhs.parent is not None or rhs.parent is not None:
                    raise LowerError("+ of lists under a macro item")
                return SList(Axis(lhs.axis.segments + rhs.axis.segments),
                             lhs.parts + rhs.parts)
            raise LowerError("+ on non-lists")
        if isinstance(ast, C.Ternary):
            return self._guarded_list(ast, env)
        if isinstance(ast, C.ListLit):
            if not ast.items:
                return SList(Axis(()), ())  # empty list literal
            items = [self.value(i, env) for i in ast.items]
            if all(isinstance(i, SLit) and isinstance(i.value, str)
                   for i in items):
                return SLit([i.value for i in items])
            raise LowerError("non-string list literal")
        if isinstance(ast, C.Macro):
            if ast.name in ("filter", "map") and ast.var2 is None \
                    and ast.body2 is None:
                target = self.value(ast.target, env)
                frozen = tuple(env.items())
                if ast.name == "map":
                    if not isinstance(target, SFiltered):
                        target = self._as_list(target)
                    if isinstance(target, (SList, SParamList, SFiltered)):
                        return SMapped(target, ast.var, ast.body, frozen)
                else:
                    target = self._as_list(target)
                    if isinstance(target, (SList, SParamList)):
                        return SFiltered(target, ast.var, ast.body, frozen)
            raise LowerError(f"macro {ast.name} in value position")
        raise LowerError(f"value {type(ast).__name__}")

    @contextmanager
    def _in_variable(self, name: str):
        """A variable's expression is closed: it sees no macro item."""
        if name in self._var_stack:
            raise LowerError(f"variable cycle at {name}")
        self._var_stack.append(name)
        saved, self._axis_stack = self._axis_stack, []
        try:
            yield self.variables[name]
        finally:
            self._axis_stack = saved
            self._var_stack.pop()

    def _resolve_variable(self, name: str, env: dict) -> SVal:
        if name == "anyObject":
            return SObj(())
        if name == "params":
            return SParam(())
        if name not in self.variables:
            raise LowerError(f"unknown variable {name}")
        with self._in_variable(name) as ast:
            return self.value(ast, {})

    def _param_src(self, path: tuple):
        ast = C.Ident("params")
        for part in path:
            ast = C.Index(ast, C.Lit(part))
        return ast

    def _as_list(self, sv: SVal) -> SVal:
        if isinstance(sv, (SList, SFiltered, SMapped, SParamList)):
            return sv
        if isinstance(sv, SObj):
            if sv.path[:1] == (REVIEW_ROOT,):
                raise LowerError("request.operation as a list")
            return SList(Axis(((sv.path,),)), (ListPart(sv.path),))
        if isinstance(sv, SItem):
            parent = sv.axis
            if parent in self._nested:
                raise LowerError("two levels of lists under a macro item")
            axis = Axis(tuple(seg + (sv.subpath,)
                              for seg in parent.segments))
            self._nested.add(axis)
            return SList(axis, (ListPart(sv.subpath, parent=parent),),
                         parent=parent)
        if isinstance(sv, SParam):
            name = self._pname(sv.path)
            d = self.derived.get(name)
            src = d.ast if d is not None else self._param_src(sv.path)
            return SParamList(name, src=src)
        raise LowerError(f"not a list: {sv}")

    def _guarded_list(self, ast: C.Ternary, env: dict) -> SVal:
        """``!has(p) ? [] : x`` / ``has(p) ? x : []``: the guard's exactly-
        false form becomes an empty_guard on the resulting list parts (the
        value is a DEFINED [] when the guard fires; a broken base chain
        still errors through the has itself)."""
        def is_empty_list(a):
            return isinstance(a, C.ListLit) and not a.items

        cond, then, other = ast.cond, ast.then, ast.other
        neg = isinstance(cond, C.Unary) and cond.op == "!"
        inner = cond.operand if neg else cond
        if not _is_call(inner, "has"):
            raise LowerError("ternary outside the has()-guard idiom")
        guarded_sv = self.value(inner.args[0], env)
        t_has, f_has = self._has_pair(guarded_sv)
        if neg and is_empty_list(then):
            taken = self.value(other, env)
        elif not neg and is_empty_list(other):
            taken = self.value(then, env)
        else:
            raise LowerError("ternary outside the has()-guard idiom")
        if isinstance(taken, SParam):
            if self._pname(taken.path) in self.derived or \
                    not isinstance(guarded_sv, SParam) or \
                    guarded_sv.path != taken.path:
                # the host computes the whole ternary, guard included
                raise LowerError("guarded parameter-side list")
            taken = self._as_list(taken)
        if isinstance(taken, SParamList):
            if taken.guarded:
                return taken
            # param-table counts already encode absence
            return SParamList(taken.name, guarded=True, src=ast)
        taken = self._as_list(taken)
        if not isinstance(taken, SList):
            raise LowerError(f"guarded non-list {taken}")
        parts = tuple(
            ListPart(p.path, p.empty_guards + (f_has,), p.parent)
            for p in taken.parts
        )
        return SList(taken.axis, parts, taken.parent)

    # --- reductions over a list's items --------------------------------
    def _part_col(self, part: ListPart):
        if part.parent is not None:
            return self._ragged_col(part.parent, part.path)
        return self._scalar_col(part.path)

    def _any(self, target: SList, inner: N.Expr) -> N.Expr:
        """∃ item of ``target`` satisfying ``inner`` (per item of the
        parent axis for a list one level down)."""
        if inner is _FALSE:
            return _FALSE
        if target.parent is None:
            return N.AnyAxis(target.axis, inner)
        picol = ParentIdxCol(axis=target.axis, parent=target.parent)
        if picol not in self.schema.parent_idx:
            self.schema.parent_idx.append(picol)
        parent_col = self._ragged_col(target.parent, ())
        # the child's own item column gives the reduction its shape where
        # the inner is item-independent
        saved, self._axis_stack = self._axis_stack, [target.axis]
        try:
            shape = N.Present(self._ragged_col(target.axis, ()))
        finally:
            self._axis_stack = saved
        return N.NestedAny(picol, parent_col, _and(shape, inner))

    def _list_ok(self, target: SList, allow_empty_map: bool) -> N.Expr:
        """The target expression evaluates to a DEFINED list (or, when
        allowed, an empty map — CEL macros over empty maps are vacuous).
        Anything else (error, non-list value, NON-empty map whose keys the
        axis cannot represent) fails both polarities → error → violation."""
        oks = []
        for part in target.parts:
            col = self._part_col(part)
            alts = list(part.empty_guards)
            alts.append(N.KindIs(col, K_OTHER))
            if allow_empty_map:
                if part.parent is None:
                    axis = Axis(((part.path,),))
                    self._touch_axis(axis)
                    nonempty = N.AnyAxis(axis, _TRUE)
                else:
                    nonempty = self._any(target, _TRUE)
                alts.append(_and(N.KindIs(col, K_MAP), N.Not(nonempty)))
            oks.append(_or(*alts))
        return _and(*oks)

    def _touch_axis(self, axis: Axis):
        """Ensure the axis's counts are materialized in the schema."""
        col = RaggedCol(axis=axis, subpath=())
        if col not in self.schema.raggeds:
            self.schema.raggeds.append(col)

    def _body(self, axis: Axis, ast, env: dict) -> tuple:
        """A macro body's pair with ``axis``'s item in scope."""
        self._axis_stack.append(axis)
        try:
            return self.bool_pair(ast, env)
        finally:
            self._axis_stack.pop()

    def _branches(self, target: SList, var: str, var2: Optional[str],
                  body, env: dict) -> list:
        """[(gate, tp, fp, item_env)] — the body's pair per runtime kind of
        a list-or-map target, kind-branched at runtime: CEL iterates a
        LIST's values but a MAP's keys, and the flattener's ragged axes
        carry both (value items + an aligned MapKeyColumn), so one axis
        serves both semantics.

        - list branch: var (or var2 of a two-variable macro) binds the
          item value; a two-variable macro's var binds the int index,
          on which every string method errors.
        - map branch (single-part targets): var binds the KEY (SMapKey →
          string ops over the MapKeyColumn); var2, when present, binds
          the value item.  Only taken when the body lowers under the key
          binding; otherwise non-empty maps gate to the error outcome,
          exact only when the body must deref the variable
          (_check_no_bare_var).
        """
        axis = target.axis
        # the reductions read the axis count column even when the body
        # never touches an item field (var-free / key-only bodies)
        if target.parent is None:
            self._touch_axis(axis)
        single = len(target.parts) == 1
        out = []
        map_branch = None
        if single:
            snap = self._snapshot()
            try:
                menv = dict(env)
                menv[var] = SMapKey(axis)
                if var2 is not None:
                    menv[var2] = SItem(axis, ())
                ktp, kfp = self._body(axis, body, menv)
                is_map = N.KindIs(self._part_col(target.parts[0]), K_MAP)
                map_branch = (is_map, ktp, kfp, menv)
            except LowerError:
                self._restore(snap)
        sub_env = dict(env)
        if var2 is None:
            sub_env[var] = SItem(axis, ())
            tp, fp = self._body(axis, body, sub_env)
            if map_branch is None and single:
                # maps gate to error: exact only if the body errors on
                # every string key (it must deref the variable)
                _check_no_bare_var(body, var)
            ok = self._list_ok(
                target, allow_empty_map=single and map_branch is None)
        else:
            # two-variable macro: over a map, (key, value); over a LIST,
            # CEL binds (index, value)
            if map_branch is None:
                raise LowerError("two-variable macro body does not lower "
                                 "under the key binding")
            sub_env[var] = SIndex()
            sub_env[var2] = SItem(axis, ())
            tp, fp = self._body(axis, body, sub_env)
            ok = self._list_ok(target, allow_empty_map=False)
        out.append((ok, tp, fp, sub_env))
        if map_branch is not None:
            out.append(map_branch)
        return out

    def _reduce(self, name: str, target: SList, gate, tp, fp) -> tuple:
        """(t, f) of a macro over one runtime-kind branch of an axis,
        from the body's dual-polarity pair.  exists_one never
        short-circuits, so BOTH its outcomes require every item defined."""
        if name == "all":
            return (_and(gate, _not(self._any(target, _not(tp)))),
                    _and(gate, self._any(target, fp)))
        if name == "exists":
            return (_and(gate, self._any(target, tp)),
                    _and(gate, _not(self._any(target, _not(fp)))))
        if name == "exists_one":
            if target.parent is not None:
                raise LowerError("exists_one under a macro item")
            axis = target.axis
            defined = N.Not(N.AnyAxis(axis, _and(_not(tp), _not(fp))))
            one = N.CountAxisIs(axis, tp, 1)
            return (_and(gate, defined, one),
                    _and(gate, defined, N.Not(one)))
        raise LowerError(f"macro {name}")

    # --- boolean lowering (dual polarity) ----------------------------
    def bool_pair(self, ast, env: dict) -> tuple:
        snap = self._snapshot()
        try:
            return self._bool_pair(ast, env)
        except LowerError:
            self._restore(snap)
            d = self._derive(ast, env)
            if d is None:
                raise
            if isinstance(d, SParam):
                name = self._pname(d.path)
                self._note_param(name, "bool")
                return N.ParamBoolIs(name, True), N.ParamBoolIs(name, False)
            num = self._num(d)  # a bool field rides as 1 / 0
            return (N.CmpNum(num, "eq", N.ConstNum(1.0)),
                    N.CmpNum(num, "eq", N.ConstNum(0.0)))

    def _bool_pair(self, ast, env: dict) -> tuple:
        if isinstance(ast, C.Lit):
            if ast.value is True:
                return _TRUE, _FALSE
            if ast.value is False:
                return _FALSE, _TRUE
            raise LowerError("non-bool literal in bool position")
        if isinstance(ast, C.Unary):
            if ast.op == "!":
                t, f = self.bool_pair(ast.operand, env)
                return f, t
            raise LowerError(f"unary {ast.op}")
        if isinstance(ast, C.Ternary):
            tc, fc = self.bool_pair(ast.cond, env)
            ta, fa = self.bool_pair(ast.then, env)
            tb, fb = self.bool_pair(ast.other, env)
            return (_or(_and(tc, ta), _and(fc, tb)),
                    _or(_and(tc, fa), _and(fc, fb)))
        if isinstance(ast, C.Binary):
            return self._binary_pair(ast, env)
        if isinstance(ast, C.Macro):
            return self._macro_pair(ast, env)
        if isinstance(ast, C.Call):
            return self._call_pair(ast, env)
        if isinstance(ast, (C.Ident, C.Select, C.Index)):
            if isinstance(ast, C.Select) and isinstance(ast.base, C.Ident) \
                    and ast.base.name == "variables" \
                    and "variables" not in env \
                    and ast.field in self.variables:
                # a boolean variable: its expression, in place
                with self._in_variable(ast.field) as var_ast:
                    return self.bool_pair(var_ast, {})
            # a bare boolean field read
            sv = self.value(ast, env)
            if isinstance(sv, (SObj, SItem)):
                col = self._feat_col(sv)
                return N.KindIs(col, K_TRUE), N.KindIs(col, K_FALSE)
            if isinstance(sv, SParam):
                name = self._pname(sv.path)
                self._note_param(name, "bool")
                return (N.ParamBoolIs(name, True),
                        N.ParamBoolIs(name, False))
            raise LowerError(f"bool read of {sv}")
        raise LowerError(f"bool {type(ast).__name__}")

    def _binary_pair(self, ast: C.Binary, env: dict) -> tuple:
        op = ast.op
        if op == "&&":
            ta, fa = self.bool_pair(ast.lhs, env)
            tb, fb = self.bool_pair(ast.rhs, env)
            return _and(ta, tb), _or(fa, fb)
        if op == "||":
            ta, fa = self.bool_pair(ast.lhs, env)
            tb, fb = self.bool_pair(ast.rhs, env)
            return _or(ta, tb), _and(fa, fb)
        if op in ("==", "!="):
            t, f = self._eq_pair(ast.lhs, ast.rhs, env)
            return (f, t) if op == "!=" else (t, f)
        if op in ("<", "<=", ">", ">="):
            ir_op = {"<": "lt", "<=": "lte", ">": "gt", ">=": "gte"}[op]
            inv = {"lt": "gte", "lte": "gt", "gt": "lte", "gte": "lt"}[ir_op]
            return self._cmp_pair(ast.lhs, ast.rhs, ir_op, inv, env)
        if op == "in":
            return self._in_pair(self.value(ast.lhs, env),
                                 self.value(ast.rhs, env), env)
        raise LowerError(f"binary {op}")

    def _param_gate(self, plist: SParamList) -> N.Expr:
        """An unguarded parameter list that is absent (or whose host
        derivation erred) is CEL's error, not an empty list."""
        if plist.guarded:
            return _TRUE
        self.weak_params.add(plist.name)
        return N.ParamPresent(plist.name)

    def _in_pair(self, needle: SVal, hay: SVal, env: dict) -> tuple:
        if isinstance(hay, (SMapKey, SIndex)):
            return _FALSE, _FALSE  # in a string or an int: an error
        if isinstance(hay, SParam):
            hay = self._as_list(hay)
        if isinstance(hay, SParamList):
            self._note_param(hay.name, "strlist")
            gate = self._param_gate(hay)
            hit = N.InStrList(self._sid(needle), hay.name)
            # heterogeneous membership: a defined non-string needle is
            # simply not in a string list (false, not error)
            return (_and(gate, hit),
                    _and(gate, self._defined(needle), N.Not(hit)))
        if isinstance(hay, SLit) and isinstance(hay.value, list):
            sid = self._sid(needle)
            hit = _or(*(N.EqStr(sid, N.ConstSid(self.vocab.intern(s)))
                        for s in hay.value))
            return hit, _and(self._defined(needle), _not(hit))
        if isinstance(hay, SMapped):
            return self._in_mapped(needle, hay)
        if isinstance(hay, (SObj, SItem)):
            # a list holds the needle among its values, a map among its
            # keys; anything else errors
            if isinstance(needle, (SItem, SMapKey)):
                raise LowerError("an item as the needle of an object list")
            target = self._as_list(hay)
            col = self._part_col(target.parts[0])
            if target.parent is None:
                self._touch_axis(target.axis)
            self._axis_stack.append(target.axis)
            try:
                n = self._sid(needle)
                in_list = N.EqStr(
                    N.FeatSid(self._ragged_col(target.axis, ())), n)
                in_keys = N.EqStr(
                    N.MapKeySid(self._map_key_col(target.axis)), n)
            finally:
                self._axis_stack.pop()
            is_list, is_map = N.KindIs(col, K_OTHER), N.KindIs(col, K_MAP)
            t = _or(_and(is_list, self._any(target, in_list)),
                    _and(is_map, self._any(target, in_keys)))
            return t, _and(_or(is_list, is_map), self._defined(needle),
                           N.Not(t))
        raise LowerError(f"in over {type(hay).__name__}")

    def _in_mapped(self, needle: SVal, hay: SMapped) -> tuple:
        """``x.f in L.filter(v, Q).map(v, v.f)`` with x the item of a macro
        over the same L, and Q reading v through ``v.f`` alone: x.f is in
        the list iff Q(x) — any other item with an equal f has Q's answer
        too.  The list itself must evaluate: Q defined on every item, f
        present on every kept one."""
        flt = hay.source
        if not (isinstance(flt, SFiltered) and isinstance(flt.source, SList)
                and isinstance(needle, SItem) and len(needle.subpath) == 1
                and needle.axis == flt.source.axis
                and flt.source.parent is None):
            raise LowerError("in over a mapped list outside the "
                             "exemptImages idiom")
        field = needle.subpath[0]
        body = hay.body
        if not (isinstance(body, C.Select) and isinstance(body.base, C.Ident)
                and body.base.name == hay.var and body.field == field
                and _reads_only_field(flt.body, flt.var, field)):
            raise LowerError("in over a mapped list outside the "
                             "exemptImages idiom")
        src = flt.source
        axis = src.axis
        # the list's own evaluation, over every item: a closed reduction
        saved, self._axis_stack = self._axis_stack, []
        try:
            fenv = dict(flt.env)
            fenv[flt.var] = SItem(axis, ())
            qt, qf = self._body(axis, flt.body, fenv)
            self._axis_stack.append(axis)
            try:
                kept_ok = N.Present(self._ragged_col(axis, (field,)))
            finally:
                self._axis_stack.pop()
            ok = self._list_ok(src, allow_empty_map=False)
            defined = _and(
                ok,
                N.Not(N.AnyAxis(axis, _and(_not(qt), _not(qf)))),
                N.Not(N.AnyAxis(axis, _and(qt, N.Not(kept_ok)))))
        finally:
            self._axis_stack = saved
        present = self._defined(needle)
        return _and(defined, present, qt), _and(defined, present, qf)

    def _size_of(self, ast, env: dict) -> Optional[SVal]:
        if _is_call(ast, "size"):
            return self._as_list(self.value(ast.args[0], env))
        return None

    def _cmp_pair(self, lhs_ast, rhs_ast, ir_op, inv_op, env) -> tuple:
        sized = self._size_of(lhs_ast, env)
        if sized is not None:
            k = self.value(rhs_ast, env)
            if isinstance(k, SLit) and k.value == 0:
                return self._size_cmp_zero(sized, ir_op)
            raise LowerError("size() compared to non-zero")
        sized = self._size_of(rhs_ast, env)
        if sized is not None:
            flip = {"lt": "gt", "lte": "gte", "gt": "lt", "gte": "lte"}
            return self._cmp_pair(rhs_ast, lhs_ast, flip[ir_op],
                                  flip[inv_op], env)
        lv = self.value(lhs_ast, env)
        rv = self.value(rhs_ast, env)
        gates = _and(self._num_gate(lv), self._num_gate(rv))
        ln, rn = self._num(lv), self._num(rv)
        return (_and(gates, N.CmpNum(ln, ir_op, rn)),
                _and(gates, N.CmpNum(ln, inv_op, rn)))

    def _body_defined(self, ast, env: dict) -> N.Expr:
        """A map body evaluates without error (a string concatenation of
        literals and string fields, or one field)."""
        if isinstance(ast, C.Lit):
            return _TRUE
        if isinstance(ast, C.Binary) and ast.op == "+":
            return self._str_defined(ast, env)
        try:
            return self._defined(self.value(ast, env))
        except LowerError as e:
            return _Poison(f"map body: {e}")

    def _str_defined(self, ast, env: dict) -> N.Expr:
        if isinstance(ast, C.Lit):
            return _TRUE if isinstance(ast.value, str) else _FALSE
        if isinstance(ast, C.Binary) and ast.op == "+":
            return _and(self._str_defined(ast.lhs, env),
                        self._str_defined(ast.rhs, env))
        try:
            return self._is_str(self.value(ast, env))
        except LowerError as e:
            return _Poison(f"map body: {e}")

    def _size_cmp_zero(self, target: SVal, ir_op: str) -> tuple:
        """size(L) <op> 0 for list targets (axis count semantics), through
        a filter and a map."""
        mapped = None
        if isinstance(target, SMapped):
            mapped, target = target, target.source
        flt = None
        if isinstance(target, SFiltered):
            flt, target = target, target.source
        if isinstance(target, SParamList):
            eq0_t, eq0_f = self._param_size_zero(target, flt, mapped)
        elif not isinstance(target, SList):
            raise LowerError(f"size() of {target}")
        elif not target.axis.segments:
            eq0_t, eq0_f = _TRUE, _FALSE  # (a filter of) the literal []
        elif flt is None and mapped is None:
            ok = self._list_ok(target,
                               allow_empty_map=len(target.parts) == 1)
            nonempty = self._any(target, _TRUE)
            eq0_t = _and(ok, N.Not(nonempty))
            eq0_f = _and(ok, nonempty)
        else:
            if flt is not None:
                var, body, env = flt.var, flt.body, dict(flt.env)
            else:
                var, body, env = mapped.var, C.Lit(True), dict(mapped.env)
            ts, fs = [], []
            for gate, tp, fp, item_env in self._branches(
                    target, var, None, body, env):
                all_false = _not(self._any(target, _not(fp)))
                some_true = self._any(target, tp)
                defined = _not(self._any(target,
                                         _and(_not(tp), _not(fp))))
                kept_ok = _TRUE
                if mapped is not None:
                    menv = dict(mapped.env)
                    menv[mapped.var] = item_env[var]
                    self._axis_stack.append(target.axis)
                    try:
                        bdef = self._body_defined(mapped.body, menv)
                    finally:
                        self._axis_stack.pop()
                    kept_ok = _not(self._any(target, _and(tp, _not(bdef))))
                ts.append(_and(gate, all_false))
                fs.append(_and(gate, some_true, defined, kept_ok))
            eq0_t, eq0_f = _or(*ts), _or(*fs)
        if ir_op == "eq":
            return eq0_t, eq0_f
        if ir_op == "neq":
            return eq0_f, eq0_t
        if ir_op == "gt":  # size > 0 ⇔ not (size == 0)
            return eq0_f, eq0_t
        if ir_op == "lte":  # size <= 0 ⇔ size == 0
            return eq0_t, eq0_f
        raise LowerError(f"size() {ir_op} 0")

    def _param_size_zero(self, plist: SParamList, flt, mapped) -> tuple:
        """size() == 0 of a parameter list through a filter whose body
        reads the object (a parameter-only one is the host's)."""
        if flt is None:
            raise LowerError("size() of a parameter list")
        name, tp, fp = self._param_body(plist, flt.var, flt.body,
                                        dict(flt.env))
        gate = self._param_gate(plist)
        all_false = N.Not(N.AnyParamList(name, _not(fp)))
        kept_ok = _TRUE if mapped is None else \
            _Poison("map over a filtered parameter list")
        return (_and(gate, all_false),
                _and(gate, N.AnyParamList(name, tp),
                     N.Not(N.AnyParamList(name, _and(_not(tp), _not(fp)))),
                     kept_ok))

    def _eq_pair(self, lhs_ast, rhs_ast, env) -> tuple:
        sized = self._size_of(lhs_ast, env) or self._size_of(rhs_ast, env)
        if sized is not None:
            other = rhs_ast if self._size_of(lhs_ast, env) is not None \
                else lhs_ast
            k = self.value(other, env)
            if isinstance(k, SLit) and k.value == 0:
                return self._size_cmp_zero(sized, "eq")
            raise LowerError("size() compared to non-zero")
        lv = self.value(lhs_ast, env)
        rv = self.value(rhs_ast, env)
        # boolean equality: x == true / x == false.  CEL equality is
        # heterogeneous: ANY defined non-matching value (other bool, string,
        # number, null) compares false — only absence errors
        for a, b in ((lv, rv), (rv, lv)):
            if isinstance(b, SLit) and isinstance(b.value, bool):
                if isinstance(a, SParam):
                    name = self._pname(a.path)
                    self._note_param(name, "bool")
                    t = N.ParamBoolIs(name, b.value)
                    return t, _and(N.ParamPresent(name), N.Not(t))
                if not isinstance(a, (SObj, SItem)):
                    raise LowerError("bool == on non-column")
                col = self._feat_col(a)
                want = K_TRUE if b.value else K_FALSE
                t = N.KindIs(col, want)
                return t, _and(N.Present(col), N.Not(t))
        # numeric equality (literal number or quantity on either side):
        # CmpNum(eq) is false on mixed types and CmpNum(neq) true — exactly
        # CEL's heterogeneous semantics — with presence/validity built into
        # the operand flags, so no extra kind gates
        if any(isinstance(x, SLit) and isinstance(x.value, (int, float))
               and not isinstance(x.value, bool) for x in (lv, rv)) or \
                any(isinstance(x, SQuantity) for x in (lv, rv)):
            ln, rn = self._num(lv), self._num(rv)
            return N.CmpNum(ln, "eq", rn), N.CmpNum(ln, "neq", rn)
        # string equality: one side must be a known-string (literal, param
        # element) so EqStr covers the true polarity; the false polarity is
        # CEL's heterogeneous equality — DEFINED operands of any type that
        # are not string-equal compare false, not error
        if not any(isinstance(x, (SLit, SParamElem, SParam,
                                  SParamElemField)) for x in (lv, rv)):
            raise LowerError("== between two object fields")
        ls, rs = self._sid(lv), self._sid(rv)
        eq = N.EqStr(ls, rs)
        return eq, _and(self._defined(lv), self._defined(rv), N.Not(eq))

    def _macro_pair(self, ast: C.Macro, env: dict) -> tuple:
        if ast.name not in ("all", "exists", "exists_one"):
            raise LowerError(f"macro {ast.name}")
        target = self.value(ast.target, env)
        if isinstance(target, (SMapKey, SIndex)):
            return _FALSE, _FALSE  # a macro over a string or an int errors
        target = self._as_list(target)
        if isinstance(target, SList):
            if not target.axis.segments:  # empty-list literal
                if ast.name == "all":
                    return _TRUE, _FALSE
                return _FALSE, _TRUE  # exists / exists_one over []
            ts, fs = [], []
            for gate, tp, fp, _env in self._branches(
                    target, ast.var, ast.var2, ast.body, env):
                t, f = self._reduce(ast.name, target, gate, tp, fp)
                ts.append(t)
                fs.append(f)
            return _or(*ts), _or(*fs)
        if isinstance(target, SParamList):
            if ast.var2 is not None:
                raise LowerError("two-variable macro over a param list")
            if ast.name == "exists_one":
                raise LowerError("exists_one over a param list")
            name, tp, fp = self._param_body(target, ast.var, ast.body, env)
            gate = self._param_gate(target)
            if ast.name == "all":
                return (_and(gate, N.Not(N.AnyParamList(name, _not(tp)))),
                        _and(gate, N.AnyParamList(name, fp)))
            return (_and(gate, N.AnyParamList(name, tp)),
                    _and(gate, N.Not(N.AnyParamList(name, _not(fp)))))
        raise LowerError(f"macro over {target}")

    def _param_body(self, plist: SParamList, var: str, body,
                    env: dict) -> tuple:
        """(the list the device iterates, tp, fp) of a parameter-list
        macro's body: natively over the list's own strings or objects, else
        over a host-derived list that carries, per element, whatever the
        body computes from the element and the parameters alone."""
        snap = self._snapshot()
        try:
            sub_env = dict(env)
            sub_env[var] = SParamElem(plist.name)
            tp, fp = self.bool_pair(body, sub_env)
            if self.param_kinds.get(plist.name) != "objlist":
                self._note_param(plist.name, "strlist")
                tp = self._bind_elem_needles(tp, plist.name)
                fp = self._bind_elem_needles(fp, plist.name)
            self._assert_no_bare_elem(tp)
            self._assert_no_bare_elem(fp)
            return plist.name, tp, fp
        except LowerError:
            self._restore(snap)
        name = f"~{len(self.derived)}"
        self.derived[name] = CelDeriveElems(
            plist.src, var, [], self._frozen_variables)
        self._note_param(name, "objlist")
        sub_env = dict(env)
        sub_env[var] = SDerivedElem(name, var)
        tp, fp = self.bool_pair(body, sub_env)
        if not self.derived[name].fields:
            # the element axis needs one array for its width
            self._sid(self._derive(C.Ident(var), sub_env))
        return name, tp, fp

    def _bind_elem_needles(self, expr: N.Expr, param: str) -> N.Expr:
        """Rewrite bare ParamElemSid StrPred needles to the table-backed
        _ElemListSid marker (build_param_table's strlist path).

        Recurses through every composite the macro body can produce —
        including AnyAxis/NestedAny, so an object-list macro nested inside
        a param-list macro (e.g. ``params.prefixes.exists(p,
        object.spec.containers.all(c, c.image.startsWith(p)))``) binds its
        needle; the kernel evaluates the [N, M, K] grid (eval_expr's
        elem-needle StrPred path handles the extra axis).  Any needle left
        bare after this pass would raise in build_param_table on EVERY
        query, so _assert_no_bare_elem turns that into a lowering-time
        fallback instead (ADVICE r2 high)."""
        if isinstance(expr, N.StrPred) and \
                isinstance(expr.needle, N.ParamElemSid):
            return N.StrPred(expr.op, expr.subject, _ElemListSid(param))
        if isinstance(expr, N.Not):
            return N.Not(self._bind_elem_needles(expr.inner, param))
        if isinstance(expr, N.And):
            return N.And(tuple(self._bind_elem_needles(t, param)
                               for t in expr.terms))
        if isinstance(expr, N.Or):
            return N.Or(tuple(self._bind_elem_needles(t, param)
                              for t in expr.terms))
        if isinstance(expr, N.AnyAxis):
            return N.AnyAxis(expr.axis,
                             self._bind_elem_needles(expr.inner, param))
        if isinstance(expr, N.NestedAny):
            return N.NestedAny(expr.col, expr.parent_col,
                               self._bind_elem_needles(expr.inner, param))
        return expr

    def _assert_no_bare_elem(self, expr: N.Expr) -> None:
        """LowerError if a bare ParamElemSid StrPred needle survived
        binding (a composite _bind_elem_needles doesn't know) — the
        template then falls back to the CEL evaluator instead of
        compiling a program that errors at query time."""
        if isinstance(expr, N.StrPred) and \
                isinstance(expr.needle, N.ParamElemSid):
            raise LowerError("unbound param-list element needle")
        for f in getattr(expr, "__dataclass_fields__", {}):
            v = getattr(expr, f)
            if isinstance(v, N.Expr):
                self._assert_no_bare_elem(v)
            elif isinstance(v, tuple):
                for t in v:
                    if isinstance(t, N.Expr):
                        self._assert_no_bare_elem(t)

    def _over_subject(self, subject: SVal, pair_of) -> tuple:
        """``pair_of(subject)``, or for ``map[key]`` its reduction over the
        one item of the map's axis whose key is ``key`` (no such item, or
        no map: both polarities false, CEL's error)."""
        if not isinstance(subject, SMapLookup):
            return pair_of(subject)
        axis = Axis(((subject.path,),))
        self._touch_axis(axis)
        key = self._sid(subject.key)
        self._axis_stack.append(axis)
        try:
            at_key = N.EqStr(N.MapKeySid(self._map_key_col(axis)), key)
            t, f = pair_of(SItem(axis, ()))
        finally:
            self._axis_stack.pop()
        return (N.AnyAxis(axis, _and(at_key, t)),
                N.AnyAxis(axis, _and(at_key, f)))

    def _call_pair(self, ast: C.Call, env: dict) -> tuple:
        if ast.target is None:
            if ast.name == "has" and len(ast.args) == 1:
                sv = self.value(ast.args[0], env)
                return self._has_pair(sv)
            if ast.name == "isQuantity" and len(ast.args) == 1:
                sv = self.value(ast.args[0], env)
                valid = N.StrFnValid(QUANTITY_FN, self._sid(sv))
                return valid, _and(self._is_str(sv), N.Not(valid))
            raise LowerError(f"call {ast.name}")
        # method calls
        if ast.name in _STR_METHODS and len(ast.args) == 1:
            subject = self.value(ast.target, env)
            needle = self.value(ast.args[0], env)
            if isinstance(subject, SIndex):
                return _FALSE, _FALSE  # an int has no string methods
            op = _STR_METHODS[ast.name]

            def pair_of(subj):
                pred = N.StrPred(op, self._sid(subj), self._sid(needle))
                return pred, _and(self._is_str(subj),
                                  self._is_str(needle), N.Not(pred))

            return self._over_subject(subject, pair_of)
        if ast.name in _QTY_CMP and len(ast.args) == 1:
            lhs = self.value(ast.target, env)
            rhs = self.value(ast.args[0], env)
            if not isinstance(lhs, SQuantity) or not isinstance(
                    rhs, SQuantity):
                raise LowerError(f"{ast.name} on non-quantity")
            op, inv = _QTY_CMP[ast.name]
            ln, rn = self._num(lhs), self._num(rhs)
            return N.CmpNum(ln, op, rn), N.CmpNum(ln, inv, rn)
        raise LowerError(f"method {ast.name}")


def _poisoned(expr) -> Optional[str]:
    if isinstance(expr, _Poison):
        return expr.reason
    for f in getattr(expr, "__dataclass_fields__", {}):
        v = getattr(expr, f)
        for x in (v if isinstance(v, tuple) else (v,)):
            if isinstance(x, N.Expr):
                why = _poisoned(x)
                if why:
                    return why
    return None


def lower_cel_template(compiled, template_kind: str, vocab,
                       schema_hint: Optional[dict] = None) -> N.Program:
    """Lower a _CompiledCELTemplate (drivers/cel_driver.py) to a Program,
    or raise LowerError (→ interpreter fallback)."""
    if compiled.match_conditions:
        raise LowerError("matchConditions")
    if compiled.failure_policy != "Fail":
        raise LowerError(f"failurePolicy {compiled.failure_policy}")
    low = _CelLowerer(compiled.variables, vocab, schema_hint)
    violations = []
    for v in compiled.validations:
        t, _f = low.bool_pair(v.expression.ast, {})
        violations.append(N.Not(t))
    expr = violations[0] if len(violations) == 1 \
        else N.Or(tuple(violations))
    why = _poisoned(expr)
    if why:
        raise LowerError(why)
    kinds = dict(low.param_kinds)
    for name in low.weak_params:
        kinds.setdefault(name, "str")
    params = []
    for name, kind in sorted(kinds.items()):
        derive = low.derived.get(name)
        if isinstance(derive, CelDeriveElems):
            derive.fields = tuple(derive.fields)
        fields = tuple(sorted(low.param_fields.get(name, {}).items()))
        params.append(N.ParamSpec(name=name, kind=kind, fields=fields,
                                  derive=derive))
    return N.Program(
        template_kind=template_kind,
        expr=expr,
        params=tuple(params),
        schema=low.schema,
        # the evaluator returns one result per validation that is not true
        # (false, or an error under failurePolicy Fail): the count is the
        # number of these, with no dedupe (drivers/cel_driver.py query)
        count=N.CountSum(tuple(N.CountIf(v) for v in violations)),
    )
