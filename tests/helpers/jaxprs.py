"""Reading a traced program: its Pallas calls by name and the matmuls a
kernel's body traces to (`LOWERED` masks a Mosaic call's body, so a count
of its `dot_general`s is what sees an operand gain or lose terms)."""


def dots_of(jaxpr) -> int:
    """How many dot_generals a jaxpr holds, its sub-jaxprs' counted once
    each (a loop's body is one iteration's)."""
    from jax._src import core
    return sum(
        (eqn.primitive.name == "dot_general")
        + sum(dots_of(sub) for sub in core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


def passes_of(jaxpr) -> int:
    """How many bfloat16 passes of the matrix unit a jaxpr's dot_generals
    take: one for a product of bfloat16 operands, six for one of float32
    operands (Precision.HIGHEST's pairs of terms); sub-jaxprs as in
    `dots_of`."""
    from jax._src import core
    return sum(
        (0 if eqn.primitive.name != "dot_general"
         else 6 if eqn.invars[0].aval.dtype == "float32" else 1)
        + sum(passes_of(sub) for sub in core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


def pallas_calls(jaxpr) -> dict:
    """name -> the body's jaxpr, for every pallas_call of a jaxpr, those
    inside its sub-jaxprs (a custom_vjp's rules, a jit) among them."""
    from jax._src import core
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn.params["jaxpr"]
        for sub in core.jaxprs_in_params(eqn.params):
            found.update(pallas_calls(sub))
    return found


def pallas_operands(jaxpr, kernel):
    """(grid, the operands' shapes, the results' shapes) of the pallas_call
    named `kernel` in a jaxpr, its sub-jaxprs searched; None if none is."""
    from jax._src import core
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == kernel):
            return (eqn.params["grid_mapping"].grid,
                    [tuple(v.aval.shape) for v in eqn.invars],
                    [tuple(v.aval.shape) for v in eqn.outvars])
        for sub in core.jaxprs_in_params(eqn.params):
            found = pallas_operands(sub, kernel)
            if found:
                return found
    return None
