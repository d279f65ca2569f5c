"""What a differentiated step's forward hands its backward, read from the
step's own jaxpr (``kept``; ``trainer.step_account()`` calls it once, on
demand: docs/timeline.md).

After ``jax.value_and_grad`` a jaxpr holds both passes side by side. An
equation whose name stack (its own, after those of the equations it is
nested in) holds ``transpose(`` belongs to the backward, as does a
differentiated ``remat2``, the recompute; every other is the forward's.
A value is kept if an equation of the forward makes it and one of the
backward reads it: it lives from the one to the other, whatever the
compiler schedules between. The jaxpr's own arguments (parameters,
optimizer state, the batch) have no equation and are left out, as are the
cotangents, which the backward makes. A counter round the checkpoint's
policy would not do: JAX asks a policy twice an equation and once more a
distinct trace.
"""

import re

import jax
from jax.extend.core import Var

# where an equation keeps the jaxpr whose arguments and results are its own
BODY = {"jit": "jaxpr", "shard_map": "jaxpr", "remat2": "jaxpr",
        "scan": "jaxpr", "cond": "branches",
        "custom_vjp_call": "call_jaxpr", "custom_jvp_call": "call_jaxpr"}
SCOPE = re.compile(r"bps\.[\w.]+")
LAYER_INPUT, UNNAMED = "layer_input", "unnamed"


def kept(closed_jaxpr) -> dict:
    """``{"bytes", "by_name": {name: [values, bytes]}, "by_scope": {scope:
    bytes}}`` of the values kept, each counted once, in bytes a device (under
    ``shard_map`` a value is a device's shard). A value's name is its
    checkpoint's (``checkpoint_name``) where a ``name`` equation made it,
    else ``layer_input`` where the recompute reads it (a checkpointed
    function's own argument), else ``unnamed`` (a custom derivative's
    residuals outside every checkpoint); its scope the innermost ``bps.*``
    of the stack of the equation that made it, ``""`` outside all. One
    value a trip where a scan stacks them."""
    by_name, by_scope = {}, {}
    for size, values, name, scope in _crossings(closed_jaxpr.jaxpr, ""):
        count = by_name.setdefault(name, [0, 0])
        count[0] += values
        count[1] += size
        by_scope[scope] = by_scope.get(scope, 0) + size
    return {"bytes": sum(by_scope.values()), "by_name": by_name,
            "by_scope": by_scope}


def _bodies(eqn) -> list:
    found = eqn.params.get(BODY.get(eqn.primitive.name), ())
    return [getattr(j, "jaxpr", j)
            for j in (found if isinstance(found, tuple) else (found,))]


def _stack(outer: str, eqn) -> str:
    return f"{outer}/{eqn.source_info.name_stack}"


def _recompute(eqn) -> bool:
    return eqn.primitive.name == "remat2" and eqn.params["differentiated"]


def _crossings(jaxpr, outer: str):
    """(bytes, values, name, scope) of every value of ``jaxpr`` and of the
    jaxprs nested in its forward that crosses from forward to backward. A
    differentiation inside a scan's body hands its values over a trip at a
    time: they count once."""
    made, seen = {}, set()
    for eqn in jaxpr.eqns:
        stack = _stack(outer, eqn)
        if "transpose(" in stack or _recompute(eqn):
            for at, var in enumerate(eqn.invars):
                root, name, scope, values = _origin(var, made, outer)
                if root is None or root in seen:
                    continue
                seen.add(root)
                if name is None:
                    name = (LAYER_INPUT if _recomputed_from(eqn, at)
                            else UNNAMED)
                scopes = SCOPE.findall(scope)
                yield (var.aval.size * var.aval.dtype.itemsize, values, name,
                       scopes[-1] if scopes else "")
            continue
        made.update((var, eqn) for var in eqn.outvars)
        for body in jax.core.jaxprs_in_params(eqn.params):
            yield from _crossings(body, stack)


def _origin(var, made: dict, outer: str):
    """(the variable, checkpoint name or None, name stack, values) where
    ``var`` was made: its ``name`` equation if it has one, found through
    the ``reduce_precision`` JAX wraps a kept float in, through a body that
    hands an operand on as a result (a jitted custom derivative's
    residuals: one buffer, counted once) and inside a body that makes it
    (a scan's stacked result is one value a trip). ``(None, ...)`` for
    what no equation of ``made`` made: an argument, a constant."""
    eqn = made.get(var) if isinstance(var, Var) else None
    if eqn is None:
        return None, None, outer, 1
    stack = _stack(outer, eqn)
    if eqn.primitive.name == "name":
        return var, eqn.params["name"], stack, 1
    if eqn.primitive.name == "reduce_precision":
        return _origin(eqn.invars[0], made, outer)
    at, trips = eqn.outvars.index(var), 1
    if eqn.primitive.name == "scan" and at >= eqn.params["num_carry"]:
        trips = eqn.params["length"]
    for body in _bodies(eqn):
        result = body.outvars[at]
        if trips == 1 and result in body.invars:
            operand = eqn.invars[len(eqn.invars) - len(body.invars)
                                 + body.invars.index(result)]
            return _origin(operand, made, outer)
        inside = {v: e for e in body.eqns for v in e.outvars}
        root, name, inner, values = _origin(result, inside, stack)
        if root is not None:
            return root, name, inner, values * trips
    return var, None, stack, trips      # a scan may stack what it is given


def _recomputed_from(eqn, at: int) -> bool:
    """Is operand ``at`` of ``eqn`` read by a recompute: ``eqn``'s own, or
    one inside its body (the backward scan of a scanned stack)."""
    if _recompute(eqn):
        return True
    for body in _bodies(eqn):
        at_body = at - (len(eqn.invars) - len(body.invars))    # a cond's index
        if at_body >= 0 and any(
                _recomputed_from(e, i) for e in body.eqns
                for i, v in enumerate(e.invars) if v is body.invars[at_body]):
            return True
    return False
