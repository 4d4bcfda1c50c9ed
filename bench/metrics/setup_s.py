"""setup_s: seconds from the process's start to the window's start (JAX
start, the cell's data, the warm-up call), on the host clock."""
from typing import Optional


def read(ctx: dict) -> Optional[float]:
    return ctx["setup_s"]
