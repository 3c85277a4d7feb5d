"""Which Pallas kernels a traced program calls, and where: read from
the jaxpr's equations (its text prints a kernel called twice once, as
a shared definition, so counting names in the text miscounts)."""

from collections import Counter


def _inner(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            jaxpr = getattr(item, "jaxpr", item)
            if hasattr(jaxpr, "eqns"):
                yield jaxpr


def kernel_calls(jaxpr) -> Counter:
    """{kernel name: calls} under ``jaxpr`` (a Jaxpr or ClosedJaxpr), a
    call inside a loop's body counted once."""
    calls = Counter()
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] += 1
        for inner in _inner(eqn):
            calls += kernel_calls(inner)
    return calls


def scans(jaxpr):
    """The bodies of every scan under ``jaxpr``, in program order (a
    scan inside a scan's body is its body's, not listed apart)."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["jaxpr"]
        else:
            for inner in _inner(eqn):
                yield from scans(inner)
