"""Batched CRUSH kernel vs scalar host mapper, widened scope (ISSUE 16):
uniform buckets (perm-choose), mixed bucket algs within one map, mixed
firstn+indep rule programs, and the per-map-object compile cache.

A file of its own beside tests/test_crush_batch.py: under the driver's
`--dist loadfile` a file is one worker's, and the two halves together
were the longest file of tier-1 (ROADMAP D10).
"""

import pytest
from test_crush_batch import WEIGHT_CASES, assert_match, build

from ceph_tpu.crush.builder import (build_hierarchy, make_erasure_rule,
                                    make_replicated_rule)
from ceph_tpu.crush.types import CrushMap
from ceph_tpu.ops.crush_kernel import compile_rule


def build_uniform(n_osds, per_host, ec_size=6):
    from ceph_tpu.crush.constants import BUCKET_UNIFORM
    m = CrushMap()
    m.max_devices = n_osds
    build_hierarchy(m, n_osds, per_host, alg=BUCKET_UNIFORM)
    rep = make_replicated_rule(m, "rep")
    ec = make_erasure_rule(m, "ec", size=ec_size)
    return m, rep, ec


@pytest.mark.parametrize("wname,wfn", WEIGHT_CASES)
@pytest.mark.parametrize("n_osds,per_host", [(12, 2), (12, 3), (8, 4)])
def test_uniform_firstn_bit_exact(n_osds, per_host, wname, wfn):
    m, rep, _ = build_uniform(n_osds, per_host)
    assert compile_rule(m, rep) is not None
    for numrep in (1, 2, 3):
        assert_match(m, rep, numrep, wfn(n_osds))


@pytest.mark.parametrize("wname,wfn", WEIGHT_CASES)
@pytest.mark.parametrize("size", [3, 4, 6])
def test_uniform_indep_bit_exact(size, wname, wfn):
    # 12 osds / 2 per host = 6 hosts: sizes 3 and 6 divide the root
    # bucket evenly (the uniform (numrep+1)*ftotal r-bump of
    # choose_indep fires); size 4 does not (plain numrep*ftotal)
    m, _, ec = build_uniform(12, 2, ec_size=size)
    assert compile_rule(m, ec) is not None
    assert_match(m, ec, size, wfn(12))


@pytest.mark.parametrize("wname,wfn", WEIGHT_CASES)
def test_uniform_leaf_bump_bit_exact(wname, wfn):
    # host size 6 with numrep 3/6: the r-bump fires on the LEAF level
    # of the chooseleaf recursion too (host.size % numrep == 0)
    m, _, _ = build_uniform(30, 6, ec_size=3)
    ec6 = make_erasure_rule(m, "ec6", size=6)
    ec3 = m.find_rule(1, 3, 3)
    assert compile_rule(m, ec3) is not None
    assert_match(m, ec3, 3, wfn(30))
    assert_match(m, ec6, 6, wfn(30))


def test_mixed_alg_levels_bit_exact():
    """straw2 root over UNIFORM hosts (and the reverse): alg is static
    PER LEVEL, so one map may mix draw kinds across levels."""
    from ceph_tpu.crush.builder import make_bucket
    from ceph_tpu.crush.constants import BUCKET_STRAW2, BUCKET_UNIFORM
    for root_alg, host_alg in ((BUCKET_STRAW2, BUCKET_UNIFORM),
                               (BUCKET_UNIFORM, BUCKET_STRAW2)):
        m = CrushMap()
        m.max_devices = 30
        hosts = []
        for h in range(5):
            items = list(range(h * 6, h * 6 + 6))
            hb = make_bucket(m, host_alg, 1, items, [0x10000] * 6)
            m.name_map[hb.id] = f"host{h}"
            hosts.append(hb)
        root = make_bucket(m, root_alg, 10, [b.id for b in hosts],
                           [b.weight for b in hosts])
        m.name_map[root.id] = "default"
        rep = make_replicated_rule(m, "rep")
        ec = make_erasure_rule(m, "ec", size=4)
        assert compile_rule(m, rep) is not None
        assert compile_rule(m, ec) is not None
        for wname, wfn in WEIGHT_CASES:
            assert_match(m, rep, 3, wfn(30))
            assert_match(m, ec, 4, wfn(30))


def test_mixed_firstn_indep_rule_bit_exact():
    """One rule program mixing a firstn segment and an indep segment
    (TAKE;CHOOSELEAF_FIRSTN;EMIT;TAKE;CHOOSELEAF_INDEP;EMIT) compiles
    and matches the scalar mapper — including the cumulative
    result_max cap landing mid-segment (indep holes included)."""
    from ceph_tpu.crush.constants import (RULE_CHOOSELEAF_FIRSTN,
                                          RULE_CHOOSELEAF_INDEP,
                                          RULE_EMIT, RULE_TAKE)
    from ceph_tpu.crush.types import Rule, RuleStep
    m, _, _ = build(24, 2)
    root = next(i for i, n in m.name_map.items() if n == "default")
    rule = Rule(ruleset=9, type=1, min_size=1, max_size=10,
                steps=[RuleStep(RULE_TAKE, root),
                       RuleStep(RULE_CHOOSELEAF_FIRSTN, 2, 1),
                       RuleStep(RULE_EMIT),
                       RuleStep(RULE_TAKE, root),
                       RuleStep(RULE_CHOOSELEAF_INDEP, 4, 1),
                       RuleStep(RULE_EMIT)])
    ruleno = m.add_rule(rule)
    assert compile_rule(m, ruleno) is not None
    for wname, wfn in WEIGHT_CASES:
        assert_match(m, ruleno, 8, wfn(24))   # both segments in full
        assert_match(m, ruleno, 5, wfn(24))   # cap lands mid-indep


def test_uniform_osdmap_every_pg_every_rule():
    """OSDMap-level parity on a uniform-alg map: EVERY pgid of every
    pool through map_pgs_batch == the scalar pg_to_up_acting_osds."""
    from ceph_tpu.crush.constants import BUCKET_UNIFORM
    from ceph_tpu.msg.types import EntityAddr
    from ceph_tpu.osd.osdmap import Incremental, OSDMap
    from ceph_tpu.osd.types import (OSD_IN_WEIGHT, PGPool,
                                    POOL_TYPE_ERASURE,
                                    POOL_TYPE_REPLICATED)
    m = OSDMap()
    m.fsid = "uniform-fsid"
    crush = CrushMap()
    crush.max_devices = 12
    build_hierarchy(crush, 12, 2, alg=BUCKET_UNIFORM)
    rep_rule = make_replicated_rule(crush, "replicated_rule")
    ec_rule = make_erasure_rule(crush, "ec_rule", size=6)
    m.crush = crush
    m.set_max_osd(12)
    inc = Incremental(1)
    for o in range(12):
        inc.new_up[o] = EntityAddr("127.0.0.1", 6800 + o, o + 1)
        inc.new_weight[o] = OSD_IN_WEIGHT
    m.apply_incremental(inc)
    m.pools[1] = PGPool(POOL_TYPE_REPLICATED, size=3,
                        crush_ruleset=rep_rule, pg_num=32)
    m.pool_names[1] = "rbd"
    m.pools[2] = PGPool(POOL_TYPE_ERASURE, size=6, min_size=5,
                        crush_ruleset=ec_rule, pg_num=32,
                        ec_profile="k4m2")
    m.pool_names[2] = "ecpool"
    inc = Incremental(m.epoch + 1)
    inc.new_weight[7] = 0x8000          # degraded: retries fire
    m.apply_incremental(inc)
    for pool in (1, 2):
        batch = m.map_pgs_batch(pool)
        assert len(batch) == 32
        for pg, up, upp, acting, actp in batch:
            assert (up, upp, acting, actp) == m.pg_to_up_acting_osds(pg)


def test_compile_cache_per_map_object():
    """Guarded compile cache: steady-state compile_rule calls against
    the SAME map object note exactly one real compile per rule; a new
    map object (epoch churn via from_bytes) recompiles once; in-place
    mutation drops the attached cache."""
    from ceph_tpu.common import devstats
    m, rep, ec = build(12, 2)

    def compiles():
        return devstats.counters()["compiles"].get("crush_compile", 0)

    base = compiles()
    assert compile_rule(m, rep) is not None
    after_first = compiles()
    assert after_first == base + 1
    for _ in range(5):                  # steady state: pure cache hits
        assert compile_rule(m, rep) is not None
    assert compiles() == after_first
    assert compile_rule(m, ec) is not None   # second rule: one more
    assert compiles() == after_first + 1

    m2 = CrushMap.from_bytes(m.to_bytes())   # epoch churn: new object
    assert compile_rule(m2, rep) is not None
    assert compiles() == after_first + 2
    assert compile_rule(m2, rep) is not None
    assert compiles() == after_first + 2

    # in-place mutation invalidates: the next call REALLY recompiles
    from ceph_tpu.crush.builder import reweight_item
    host0 = m2.bucket(next(i for i, n in m2.name_map.items()
                           if n == "host0"))
    reweight_item(m2, host0, 0, 0x8000)
    assert not hasattr(m2, "_kernel_compile_cache")
    assert compile_rule(m2, rep) is not None
    assert compiles() == after_first + 3
