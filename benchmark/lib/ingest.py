"""The ingest job: a child process that appends TPC-H RF1 batches to the
Delta tables on a clock, as a streaming writer does, while queries run.

Usage: ``python ingest.py <plan.json> <out.json>``.  It makes the plan's
batches first (``tpch.rf1_batch``, the same rows for the same seed),
prints ``READY``, waits for ``GO`` on stdin and then, every
``interval_s`` until ``seconds`` have passed, commits the next batch:
one Delta commit to ``orders``, then one to ``lineitem`` (an order is
visible no later than its lineitems).  Each commit is acknowledged when
the program's writer returns; the output lists, per commit, the table,
the batch, the version written and ``acked``, ``time.monotonic()`` at
the acknowledgement (the host's one monotonic clock, which the load
generator's send times share).

It never touches JAX's devices (``JAX_PLATFORMS=cpu`` is set for it):
the chip belongs to the server's process.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tpch  # noqa: E402

TABLES = ("orders", "lineitem")  # commit order within a batch


def main(plan_path: str, out_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)
    sys.path.insert(0, plan["root"])
    interval, seconds = float(plan["interval_s"]), float(plan["seconds"])
    first = int(plan["first_batch"])
    count = int(seconds // interval)
    batches = [tpch.rf1_batch(int(plan["seed"]), first + k,
                              float(plan["scale_factor"]),
                              int(plan["orders_per_sf"]))
               for k in range(count)]
    # The writer's imports, before READY: the window starts at GO.
    import hyperspace_tpu.sources.delta.writer  # noqa: F401

    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    t0 = time.monotonic()
    commits = []
    for k, batch in enumerate(batches):
        time.sleep(max(0.0, t0 + (k + 1) * interval - time.monotonic()))
        for name in TABLES:
            version = tpch.append_delta(batch[name], plan["paths"][name])
            commits.append({"table": name, "batch": first + k,
                            "version": int(version),
                            "acked": time.monotonic()})
    with open(out_path, "w") as f:
        json.dump({"t0": t0, "commits": commits}, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
