"""step_mfu: the training operations of the window's node-rounds over the
chips' busy time in the traced window and the chip's bf16 peak, in
percent.

The busy time is the union of the operations on each chip's ``XLA Ops``
line inside the window (``bench.tracefile``), summed over the chips: the
device time of every program the window's calls ran. Host time in which
the chips wait is left out; ``device_idle_share`` reads that part. None
in a run without a trace.
"""
from typing import Optional


def read(ctx: dict) -> Optional[float]:
    trace = ctx["trace"]
    if trace is None or ctx["flops_per_node_round"] is None \
            or ctx["peak"] is None or trace["busy_s"] <= 0:
        return None
    done = ctx["node_rounds"] * ctx["flops_per_node_round"]
    return 100.0 * done / (trace["busy_s"] * ctx["chips"]
                           * ctx["peak"]["bf16_flops_per_s"])
