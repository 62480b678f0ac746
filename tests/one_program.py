"""One program a comparison, for the interpreter tests (not collected as
tests). ``jax.vjp(fn, *args)`` dispatched op by op compiles every reshape,
cast, scan and kernel of a case's own shape by itself, and on the CPU those
compiles — not the arithmetic at these sizes — are what a case costs. Under
one ``jax.jit`` a case is one trace and one compile, and the forward runs
once."""

import jax


def value_and_pullback(fn, args, cot):
    """``(fn(*args), the pull-back of cot)`` as one jitted program; the
    gradients come in the order of ``args``."""
    def run(args, cot):
        value, pull = jax.vjp(fn, *args)
        return value, pull(cot)
    return jax.jit(run)(tuple(args), cot)
