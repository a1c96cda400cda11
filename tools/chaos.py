#!/usr/bin/env python
"""Seeded fleet chaos drill launcher.

The engine lives at :mod:`hyperspace_tpu.interop.chaos` (importable from
tests); this shim makes it runnable from a checkout without an
install::

    python tools/chaos.py --seed 7 --duration 8
    python tools/chaos.py --seed 7 --schedule-only   # print the plan

Exit status 0 iff every invariant held (zero lost requests, bit-equal
answers, exactly-once maintenance, consistent client.* accounting).
"""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    sys.path.insert(0, _ROOT)
    from hyperspace_tpu.interop.chaos import main as chaos_main

    return chaos_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
