#!/usr/bin/env python3
"""Cut a small sample out of the profiler trace a `--trace 1` run left under
.bench_out/<cell>/trace, as the recorded traces of tests/benchmark/data were
made.  Run by hand, after that run and in its checkout.

  python3 benchmark/trace_sample.py <cell> <out.json> [events]
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmark.lib.trace import Trace, find_xplane

    cell, out = argv[1], argv[2]
    events = int(argv[3]) if len(argv) > 3 else 300
    tr = Trace.from_xplane(find_xplane(
        os.path.join(ROOT, ".bench_out", cell, "trace")))
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(tr.sample(events), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
