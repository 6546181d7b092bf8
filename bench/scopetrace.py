#!/usr/bin/env python3
"""Device time of one cell by the names the program gives its own work.

    python3 bench/scopetrace.py --workload <cell> --seed <n>

Sets the cell up as ``bench/run.py`` does, traces its ``trace_steps``
steps and prints the time by (direction, stage, kind) on standard error
and one JSON line on standard output (:func:`bench.scopereduce.main`).
Runs only on a TPU.  The compile cache is keyed by ``op_name`` metadata
here, so the first run after a change to the program compiles anew.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import scopereduce  # noqa: E402

if __name__ == "__main__":
    sys.exit(scopereduce.main(root=ROOT))
