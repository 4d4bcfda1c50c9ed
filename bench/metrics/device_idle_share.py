"""device_idle_share: 1 - busy / window over the traced window, averaged
over the chips, in percent. None in a run without a trace."""
from typing import Optional


def read(ctx: dict) -> Optional[float]:
    if ctx["trace"] is None:
        return None
    return 100.0 * ctx["trace"]["idle_share"]
