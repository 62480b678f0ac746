"""One program a comparison, for the interpreter tests (not collected as
tests). ``jax.vjp(fn, *args)`` dispatched op by op compiles every reshape,
cast, scan and kernel of a case's own shape by itself, and on the CPU those
compiles — not the arithmetic at these sizes — are what a case costs. Under
one ``jax.jit`` a case is one trace and one compile, and the forward runs
once."""

import jax


def pallas_calls(fn, *args):
    """``{kernel's name: its equation}`` over the ``pallas_call``s of
    ``fn``'s jaxpr, nested ones too (nothing runs: ``args`` may be
    shapes)."""
    seen = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                seen[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


def value_and_pullback(fn, args, cot):
    """``(fn(*args), the pull-back of cot)`` as one jitted program; the
    gradients come in the order of ``args``."""
    def run(args, cot):
        value, pull = jax.vjp(fn, *args)
        return value, pull(cot)
    return jax.jit(run)(tuple(args), cot)
