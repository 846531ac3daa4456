#!/usr/bin/env python3
"""Run one cell of the benchmark on the machine this starts on.

    python3 bench/run.py --workload ea3d_1m.anneal --seed 12345 \
        --seconds 10 --trace 0

Prints progress on earlier lines and, as the last line of standard output,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or its per-layer metrics with ``--trace 1``),
``device``, ``breakdown`` (traced runs) and ``checks``, the numbers that
decided ``correct`` beside their limits.  Exits 2 without a result when
JAX finds no TPU or fewer chips than the cell asks for.
"""

import sys

from harness import main

if __name__ == "__main__":
    sys.exit(main())
