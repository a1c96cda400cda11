"""``loadgen.py``'s closed loop, reporting the clock it started on.

Usage as ``loadgen.py``: ``python loadgen_clock.py <plan.json>
<out.pickle>``.  The pickle also holds ``t0``, ``time.monotonic()`` at
``GO``: the host's one monotonic clock, so ``t0 + sent`` is a request's
send time on the clock another process (the ingest job) stamps its
acknowledgements with.  It imports no JAX.
"""

from __future__ import annotations

import json
import os
import pickle
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from loadgen import _run_closed  # noqa: E402
from wire import WireClient  # noqa: E402


def main(plan_path: str, out_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)
    clients = [WireClient(plan["address"], float(plan["timeout_s"]))
               for _ in plan["streams"]]
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    t0 = time.monotonic()
    records: list = []
    answers: dict = {}
    _run_closed(plan, clients, t0, records, answers)
    for c in clients:
        c.close()
    with open(out_path, "wb") as f:
        pickle.dump({"t0": t0, "records": records, "answers": answers}, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
