"""What a traced function lowers to, read from its jaxpr: the Pallas
kernels it calls, by the name each `pallas_call` carries, and the size of
each kernel's body. The tests ask this where they would otherwise ask a
hook in the op which implementation it chose."""
from collections import Counter

import jax


def pallas_eqns(jaxpr):
    """Every `pallas_call` equation of a jaxpr, nested bodies included, as
    often as it is called."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_eqns(sub)


def calls_in(jaxpr) -> Counter:
    """Counter of kernel names over `pallas_eqns`."""
    return Counter(eqn.params["name"] for eqn in pallas_eqns(jaxpr))


def pallas_calls(fn, *args) -> Counter:
    """The kernels fn(*args) calls, by name, each as often as it is
    called (a function under `jax.grad` includes its backward pass)."""
    return calls_in(jax.make_jaxpr(fn)(*args).jaxpr)


def count_eqns(jaxpr) -> int:
    """Equations of a jaxpr, nested bodies included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += count_eqns(sub)
    return n


def kernel_sizes(jaxpr) -> dict:
    """{kernel name: equations in its body}."""
    return {eqn.params["name"]: count_eqns(eqn.params["jaxpr"])
            for eqn in pallas_eqns(jaxpr)}


def primitives_in(jaxpr) -> Counter:
    """Counter of the primitives of a jaxpr's equations, nested bodies
    included (a `pallas_call`'s own body among them)."""
    found = Counter()
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += primitives_in(sub)
    return found
