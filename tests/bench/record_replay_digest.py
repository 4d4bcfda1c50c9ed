#!/usr/bin/env python3
"""Record the reference's outputs that ``test_bench_reference.py``
compares with: each configuration's replay at its test size
(``small_cells.replay_small``), in float32 and in the bfloat16 of the
control, on the CPU.

    JAX_PLATFORMS=cpu python3 tests/bench/record_replay_digest.py \\
        [--bench-root DIR] [--out FILE]

``--bench-root`` names the checkout whose ``bench`` package replays (by
default this one), so that the outputs of one commit's reference can be
recorded for another's tests.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
SEED = 2_147_483_401


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bench-root", default=str(HERE.parents[1]))
    ap.add_argument("--out", default=str(HERE / "data" / "replay_digest.json"))
    args = ap.parse_args()
    sys.path[:0] = [args.bench_root, str(HERE), str(HERE.parents[1] / "src")]
    import small_cells as sc

    out = {"seed": SEED}
    for config in sorted(sc.REPLAY_TRAFFIC):
        for dtype in ("float32", "bfloat16"):
            got = sc.replay_small(config, SEED, dtype)
            out[f"{config}/{dtype}"] = {
                str(r): {k: [float(x) for x in v] for k, v in m.items()}
                for r, m in got.items()}
    Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
