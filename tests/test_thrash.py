"""Stochastic model checking under thrashing (RadosModel analog).

Runs ceph_tpu/qa/rados_model.py seeds in-process — randomized
write/delete/read workloads raced against osd kills, restarts, out/in
flaps and false down marks, with object-level verification against an
in-memory model.  This file is the replicated sweep; the EC sweep is
tests/test_thrash_ec.py and the targeted backfill / throttle cases are
tests/test_thrash_targeted.py: one file each, because the driver's
`--dist loadfile` gives a file to one worker.
"""

import asyncio
import os

import pytest

from ceph_tpu.qa.rados_model import run_model

# the standalone runner covers many more: python -m ceph_tpu.qa.rados_model
SEEDS = range(1, 1 + int(os.environ.get("THRASH_SEEDS", "6")))

# seed 5's kill pattern replays ~48 s of recovery wall time and pins
# no named regression (1-4, 6 keep the default-tier churn coverage);
# it runs in the slow tier with the EC role-change seed
# (tests/test_thrash_ec.py)
_REP_SLOW = {5}
SEEDS = [pytest.param(s, marks=pytest.mark.slow) if s in _REP_SLOW
         else s for s in SEEDS]


# run_model's own worst case (its docstring) plus a margin: the limit
# from outside (tests/conftest.py) only ends what the budgets missed
@pytest.mark.time_limit(630)
@pytest.mark.parametrize("seed", SEEDS)
def test_model_checker_replicated(seed):
    res = asyncio.run(run_model(seed, rounds=60))
    assert res["ok"], res["failures"]
