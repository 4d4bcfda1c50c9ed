"""entry_prep_s: host seconds a call of the program's span
``repro.sweep.prep`` (``run_sweep_cells``'s data split, batchers, test
sets, sample bank, index schedule, coefficients and inits) inside the
traced window. None in a run without a trace."""
from typing import Optional


def read(ctx: dict) -> Optional[float]:
    spans = ctx["spans"]
    if spans is None or "repro.sweep.prep" not in spans:
        return None
    return spans["repro.sweep.prep"] / ctx["calls"]
