"""What a loss's forward hands its backward, listed from shapes alone.

``residuals(loss, params, batch)`` traces ``jax.vjp`` of ``loss`` with respect
to ``params`` under ``jax.make_jaxpr`` (arguments may be
``jax.ShapeDtypeStruct``s: nothing is compiled and no array is made). The
pullback is a pytree whose leaves are the values the forward keeps, so they
are the traced function's outputs; each is followed back to the equation that
made it, through ``jit``, ``jax.checkpoint``, ``shard_map``, ``custom_vjp`` /
``custom_jvp`` calls and into a ``scan``'s body (a scanned layer loop hands over one stacked
value a residual), which gives it

* a name: the ``jax.ad_checkpoint.checkpoint_name`` where the value has one
  (the names a ``save_only_these_names`` policy keeps), else the primitive;
* the program's scope path: the ``jax.named_scope``s on the equation's name
  stack and on those of the equations it is nested in, unwrapped from
  ``jvp(...)`` / ``transpose(...)`` as an ``op_name`` is (docs/OBSERVABILITY.md,
  "Names in a profile"), from the first of ``scopes.SCOPES`` on and held to the
  names ``utils/scopes.py`` knows; ``("unscoped",)`` where there is none.

Three kinds of leaf are no activation and are summed apart: the function's own
arguments (the parameters are state, the batch is the input), values computed
from no argument at all (``constants``: an ``iota``, a mask made from shapes,
which XLA folds or makes again) and values computed from the parameters alone
(``weights``: a cast or a transpose of a leaf, whose size does not follow the
batch).
"""

import functools
import math
import operator
import re

import jax

from . import scopes

UNSCOPED = ("unscoped",)
_WRAPPER = re.compile(r"^\w+\((.*)\)$")
# (the parameter that holds the called jaxpr) of the primitives that call one
# with their own operands and results, one for one
_CALLS = {"jit": "jaxpr", "closed_call": "call_jaxpr", "checkpoint": "jaxpr",
          "custom_jvp_call": "call_jaxpr", "custom_vjp_call": "call_jaxpr", "scan": "jaxpr",
          "shard_map": "jaxpr"}
# what hands its operand on unchanged: how a policy marks what it saves, a placement
_SAME_VALUE = ("reduce_precision", "sharding_constraint")
PARAMS, BATCH = 1, 2  # what a value was computed from, as bits


def scope_path(name_stacks):
    """The program's scope path of an equation from its name stacks, outermost
    first: ('attention', 'kda', 'kda_prep'), or ``UNSCOPED``."""
    names = []
    for stack in name_stacks:
        for component in str(stack).split("/"):
            while wrapped := _WRAPPER.match(component):
                component = wrapped.group(1)
            names.append(component)
    start = next((i for i, name in enumerate(names) if name in scopes.SCOPES), None)
    if start is None:
        return UNSCOPED
    return tuple(name for name in names[start:] if name in scopes.NAMES)


def _called(eqn):
    key = _CALLS.get(eqn.primitive.name)
    if key is None or key not in eqn.params:
        return None
    sub = eqn.params[key]
    return getattr(sub, "jaxpr", sub)


def _is_var(atom):
    return not hasattr(atom, "val")  # a Literal carries its value


def _flow(jaxpr, incoming):
    """{var: bits} of every variable of ``jaxpr`` given its inputs' bits: a
    value is computed from what its equation's operands were. A called jaxpr is
    followed result by result; a ``scan``'s carry to its fixed point."""
    bits = dict(zip(jaxpr.invars, incoming))  # a constant of the jaxpr is computed from nothing

    def of(atom):
        return bits.get(atom, 0) if _is_var(atom) else 0

    for eqn in jaxpr.eqns:
        operands = [of(a) for a in eqn.invars]
        sub = _called(eqn)
        if sub is None or len(sub.invars) != len(operands):
            results = [functools.reduce(operator.or_, operands, 0)] * len(eqn.outvars)
        elif eqn.primitive.name == "scan":
            carry = slice(eqn.params["num_consts"],
                          eqn.params["num_consts"] + eqn.params["num_carry"])
            while True:
                results = _results(sub, operands)
                merged = [a | b for a, b in zip(operands[carry], results[:carry.stop - carry.start])]
                if merged == operands[carry]:
                    break
                operands[carry] = merged
        else:
            results = _results(sub, operands)
        bits.update(zip(eqn.outvars, results))
    return bits


def _results(jaxpr, incoming):
    bits = _flow(jaxpr, incoming)
    return [bits.get(v, 0) if _is_var(v) else 0 for v in jaxpr.outvars]


def _origin(jaxpr, var, stacks, outer, made_by):
    """(name, name stacks) of the equation that made ``var`` in ``jaxpr``.
    ``outer(index)`` says where the jaxpr's ``index``-th input came from, as
    (jaxpr, var or None for a literal, stacks, outer): the caller's operand,
    or for a ``scan``'s carry what the body hands the next iteration.
    ``made_by`` keeps each jaxpr's {result: equation} between calls."""
    while True:
        if id(jaxpr) not in made_by:
            made_by[id(jaxpr)] = {o: e for e in jaxpr.eqns for o in e.outvars}
        eqn = made_by[id(jaxpr)].get(var)
        if eqn is None:
            index = next((i for i, v in enumerate(jaxpr.invars) if v is var), None)
            if index is None or outer is None:
                return "argument", stacks
            jaxpr, var, stacks, outer = outer(index)
            if var is None:
                return "literal", stacks
            continue
        here = stacks + (eqn.source_info.name_stack,)
        if eqn.primitive.name == "name":
            return eqn.params["name"], here
        if eqn.primitive.name in _SAME_VALUE and _is_var(eqn.invars[0]):
            var = eqn.invars[0]
            continue
        sub = _called(eqn)
        if sub is None or len(sub.outvars) != len(eqn.outvars):
            return eqn.primitive.name, here
        inner = sub.outvars[next(i for i, o in enumerate(eqn.outvars) if o is var)]
        if not _is_var(inner):
            return "literal", here
        jaxpr, var, stacks, outer = sub, inner, here, _operand_of(eqn, sub, jaxpr, stacks, outer, here)


def _operand_of(eqn, sub, jaxpr, stacks, outer, here):
    consts = eqn.params["num_consts"] if eqn.primitive.name == "scan" else 0
    carries = eqn.params["num_carry"] if eqn.primitive.name == "scan" else 0

    def operand(index):
        handed_on = sub.outvars[index - consts] if consts <= index < consts + carries else None
        if handed_on is not None and handed_on is not sub.invars[index] and _is_var(handed_on):
            return sub, handed_on, here, operand
        atom = eqn.invars[index]
        return jaxpr, atom if _is_var(atom) else None, stacks, outer

    return operand


def residuals(loss, params, batch, has_aux=False):
    """-> {"entries": [(scope_path, name, shape, dtype, bytes)], largest first,
    "constants": bytes, "weights": bytes} for ``loss(params, *batch)``
    differentiated with respect to ``params``."""
    n_params = len(jax.tree.leaves(params))

    def pullback(params, *batch):
        return jax.vjp(lambda p: loss(p, *batch), params, has_aux=has_aux)[1]

    jaxpr = jax.make_jaxpr(pullback)(params, *batch).jaxpr
    bits = _flow(jaxpr, [PARAMS] * n_params + [BATCH] * (len(jaxpr.invars) - n_params))
    arguments = set(jaxpr.invars)
    out = {"entries": [], "constants": 0, "weights": 0}
    seen, made_by = set(), {}
    for var in jaxpr.outvars:
        if not _is_var(var) or var in arguments or var in seen:
            continue
        seen.add(var)
        nbytes = math.prod(var.aval.shape) * var.aval.dtype.itemsize
        computed_from = bits.get(var, 0)
        if not computed_from & BATCH:
            out["weights" if computed_from else "constants"] += nbytes
            continue
        name, stacks = _origin(jaxpr, var, (), None, made_by)
        out["entries"].append(
            (scope_path(stacks), name, tuple(var.aval.shape), str(var.aval.dtype), nbytes))
    out["entries"].sort(key=lambda e: -e[4])
    return out
