"""node_rounds_per_s: node-rounds completed over the whole window (the
first call's start to the last call's return), on the host clock."""
from typing import Optional


def read(ctx: dict) -> Optional[float]:
    return ctx["node_rounds"] / ctx["window_s"]
