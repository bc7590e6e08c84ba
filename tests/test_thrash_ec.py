"""The model checker's EC sweep (see tests/test_thrash.py)."""

import asyncio
import os

import pytest

from ceph_tpu.qa.rados_model import run_model

# EC churn seeds.  101 drove six earlier fixes; 105 is the regression
# seed for the role-change wedge (an EC shard moving osd slots, e.g.
# s2 -> s0 on one osd, left a newborn primary starved of peering
# replies behind its own old-shard stray) and for the backfill-cursor
# read gate (a mid-backfill replica must serve versioned objects it
# holds and answer EAGAIN — never ENOENT — for names past its cursor).
# Widen locally with EC_SEEDS=10; the standalone runner covers more:
# python -m ceph_tpu.qa.rados_model --ec --seeds 10
_N_EC = int(os.environ.get("EC_SEEDS", "2"))
EC_SEEDS = [101, 105] if _N_EC <= 2 else list(range(101, 101 + _N_EC))

# Seed 105 replays the role-change wedge end to end (~150 s wall); it
# stays required coverage but runs in the slow tier so the default
# sweep fits its time budget.  python -m ceph_tpu.qa.rados_model --ec
# still covers it, as does pytest without `-m 'not slow'`.
_EC_SLOW = {105}
EC_SEEDS = [
    pytest.param(s, marks=pytest.mark.slow) if s in _EC_SLOW else s
    for s in EC_SEEDS
]


# run_model's own worst case (its docstring) plus a margin: the limit
# from outside (tests/conftest.py) only ends what the budgets missed
@pytest.mark.time_limit(630)
@pytest.mark.parametrize("seed", EC_SEEDS)
def test_model_checker_ec_pool(seed):
    # required (no xfail) since the per-object backfill-cursor +
    # shard-aware primariness work: the historical ~1/6-seed ENOENT
    # window came from cursor-blind replicas serving holes as
    # deletions and from role-changed primaries wedging mid-recovery
    res = asyncio.run(run_model(
        seed, rounds=50, n_osds=5,
        pool_kw={"pool_type": "erasure", "k": 2, "m": 2}))
    assert res["ok"], res["failures"]
