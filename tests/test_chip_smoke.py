"""CPU rehearsal of chip_smoke.py.

The phases run here on a tiny generated dataset: build, the four queries
in-session and through QueryServer, the reference comparison, and the
four-chip comparison on the suite's virtual mesh.  The device assertions
are left out through ``check_device=False``; the routing thresholds are
still 0, so the device formulations run (on XLA:CPU).  The script itself
must refuse to run without a TPU.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_one_chip_phases_match_the_reference(tmp_path):
    out = chip_smoke.one_chip(str(tmp_path), seed=3, sf=0.002, files=4,
                              num_buckets=8, check_device=False)
    strategies = out["strategies"]
    for op in ("filter", "join", "aggregate"):
        assert strategies[op], op
        assert all(s.startswith("device") for s in strategies[op]), \
            (op, strategies[op])
    assert out["builds"][chip_smoke.LI_INDEX]["device_kernel_s"] > 0
    assert out["data"]["lineitem_rows"] > out["data"]["orders_rows"]


def test_four_chip_phases_match_one_device(tmp_path):
    out = chip_smoke.four_chips(str(tmp_path), seed=5, sf=0.002, files=4,
                                num_buckets=8, check_device=False)
    assert all(n > 0 for n in out["buckets"].values()), out["buckets"]


def test_generator_hits_sf1_cardinalities_and_domains():
    import numpy as np

    rng = np.random.default_rng(0)
    counts = chip_smoke._lines_per_order(rng, 1000, 4321)
    assert counts.sum() == 4321
    assert counts.min() >= 1 and counts.max() <= 7


def test_compare_rejects_a_changed_answer():
    import pyarrow as pa

    want = pa.table({"k": [1, 2], "v": [1.0, 2.0]})
    chip_smoke.compare("join", want, want)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare("join", want, pa.table({"k": [1, 3],
                                                   "v": [1.0, 2.0]}))
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare("q3", want, pa.table({"k": [1, 2],
                                                 "v": [1.0, 2.5]}))


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_repo", "script_alone"])
def test_script_without_a_tpu_fails_and_prints_no_result(tmp_path, alone):
    """The child is pinned to the CPU (it must not contend for a chip),
    which is exactly the no-TPU case the script has to refuse."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if alone:
        cwd = str(tmp_path)
        script = shutil.copy(script, cwd)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if not alone:
        assert "no TPU backend" in r.stderr
