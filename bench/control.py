"""Readings for a cell's correctness limits: the program's and the
control's, over several seeds, in one process on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3 [--control]

Without ``--control`` each seed is one ordinary run of the cell (set-up,
a short window, the check).  With ``--control`` the reference's control,
computed one precision step below the configuration's, stands in the
program's place in the window, and the same check reads it.  Each seed
prints one JSON line with the numbers the check compared; a limit belongs
above every program reading and below every control reading.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as R  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args()
    for seed in a.seeds.split(","):
        args = R.parse(["--workload", a.workload, "--seed", seed,
                        "--seconds", str(a.seconds), "--trace", "0"])
        try:
            res = R.run(args, control=a.control)
            line = {"seed": int(seed), "control": a.control,
                    "correct": res["correct"], "attempted": res["attempted"],
                    "check": res["check"]}
        except R.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        except Exception as e:        # noqa: BLE001 — a crash is a reading
            line = {"seed": int(seed), "control": a.control,
                    "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(line), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
