"""local_train_share: device time under the scope ``local_train`` (every
op whose scope path holds it, scopes nested in it included) over the busy
time of the traced window, averaged over the chips, in percent
(``bench.scopes``). None in a run without a trace."""
from typing import Optional

from bench import scopes


def read(ctx: dict) -> Optional[float]:
    return scopes.share(ctx, "local_train")
