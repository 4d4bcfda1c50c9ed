"""eval_share: device time under the scope ``eval`` (every op whose scope
path holds it) over the busy time of the traced window, averaged over the
chips, in percent (``bench.scopes``). None in a run without a trace."""
from typing import Optional

from bench import scopes


def read(ctx: dict) -> Optional[float]:
    return scopes.share(ctx, "eval")
