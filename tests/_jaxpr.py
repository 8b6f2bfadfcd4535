"""Jaxpr introspection shared by the dispatch-count and precision tests."""

from jax.extend.core import ClosedJaxpr, Jaxpr


def equations(jaxpr):
    """Every equation of a jaxpr, recursing into sub-jaxprs (jit /
    shard_map / scan bodies, Pallas kernel bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for u in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(u, ClosedJaxpr):
                    yield from equations(u.jaxpr)
                elif isinstance(u, Jaxpr):
                    yield from equations(u)


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of a primitive anywhere in a jaxpr."""
    return sum(eqn.primitive.name == name for eqn in equations(jaxpr))


def count_pallas_calls(jaxpr) -> int:
    """Pallas dispatches in a jaxpr, recursing through pjit/scan/etc."""
    return count_primitive(jaxpr, "pallas_call")
