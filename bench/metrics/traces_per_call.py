"""traces_per_call: JAX's jaxpr traces a call in the window, from JAX's
monitoring events (the harness's ``Clock``): each call builds a new sweep
engine and model closures, and traces their programs again."""
from typing import Optional

TRACES = "/jax/core/compile/jaxpr_trace_duration"


def read(ctx: dict) -> Optional[float]:
    return ctx["counts"].get(TRACES, 0) / ctx["calls"]
