"""engine_host_s: host seconds a call of the sweep engine's spans
``repro.engine.prepare`` and ``repro.engine.chunk`` inside the traced
window: input preparation and the chunk loop, whose first chunk in a call
holds its trace, lowering and cache load. None in a run without a
trace."""
from typing import Optional


def read(ctx: dict) -> Optional[float]:
    spans = ctx["spans"]
    if spans is None or "repro.engine.chunk" not in spans:
        return None
    return (spans.get("repro.engine.prepare", 0.0)
            + spans["repro.engine.chunk"]) / ctx["calls"]
