"""Batched CRUSH kernel vs scalar host mapper: bit-exact equivalence.

The masked fixed-trip reformulation (ops/crush_kernel.py) must return
EXACTLY what crush/mapper.py's sequential loops return for every input —
including degraded weight vectors (outed osds, fractional reweights)
where the retry/collision paths actually fire.
"""

import numpy as np
import pytest

from ceph_tpu.crush.builder import (build_hierarchy, make_erasure_rule,
                                    make_replicated_rule)
from ceph_tpu.crush.mapper import do_rule
from ceph_tpu.crush.types import CrushMap
from ceph_tpu.ops.crush_kernel import batch_do_rule, compile_rule

N_X = 512


def build(n_osds, per_host, ec_size=6):
    m = CrushMap()
    m.max_devices = n_osds
    build_hierarchy(m, n_osds, per_host)
    rep = make_replicated_rule(m, "rep")
    ec = make_erasure_rule(m, "ec", size=ec_size)
    return m, rep, ec


def assert_match(m, rule, numrep, weights, xs=None):
    xs = xs if xs is not None else list(range(N_X))
    got = batch_do_rule(m, rule, xs, numrep, weights)
    want = [do_rule(m, rule, x, numrep, weights) for x in xs]
    mism = [(x, w, g) for x, w, g in zip(xs, want, got) if w != g]
    assert not mism, f"{len(mism)} mismatches, first: {mism[:3]}"


WEIGHT_CASES = [
    ("all-in", lambda n: [0x10000] * n),
    ("one-out", lambda n: [0] + [0x10000] * (n - 1)),
    ("three-out", lambda n: [0, 0x10000, 0, 0x10000, 0] +
        [0x10000] * (n - 5)),
    ("fractional", lambda n: [(0x4000 + 0x2000 * (i % 7)) & 0xFFFF or
                              0x10000 for i in range(n)]),
    ("mixed", lambda n: [0 if i % 5 == 0 else
                         (0x8000 if i % 3 == 0 else 0x10000)
                         for i in range(n)]),
]


@pytest.mark.parametrize("wname,wfn", WEIGHT_CASES)
@pytest.mark.parametrize("n_osds,per_host", [(12, 2), (8, 1), (15, 3)])
def test_firstn_bit_exact(n_osds, per_host, wname, wfn):
    m, rep, _ = build(n_osds, per_host)
    assert compile_rule(m, rep) is not None
    for numrep in (1, 2, 3):
        assert_match(m, rep, numrep, wfn(n_osds))


@pytest.mark.parametrize("wname,wfn", WEIGHT_CASES)
@pytest.mark.parametrize("n_osds,per_host,size", [(12, 2, 6), (8, 1, 6),
                                                  (9, 1, 4)])
def test_indep_bit_exact(n_osds, per_host, size, wname, wfn):
    m, _, ec = build(n_osds, per_host, ec_size=size)
    assert compile_rule(m, ec) is not None
    assert_match(m, ec, size, wfn(n_osds))


def test_uneven_host_sizes():
    # hosts of different sizes exercise the padded-items masking
    m = CrushMap()
    m.max_devices = 11
    from ceph_tpu.crush.builder import make_bucket
    from ceph_tpu.crush.constants import BUCKET_STRAW2
    sizes = [1, 2, 3, 5]
    start = 0
    hosts = []
    for h, sz in enumerate(sizes):
        items = list(range(start, start + sz))
        start += sz
        hb = make_bucket(m, BUCKET_STRAW2, 1, items, [0x10000] * sz)
        m.name_map[hb.id] = f"host{h}"
        hosts.append(hb)
    root = make_bucket(m, BUCKET_STRAW2, 10, [b.id for b in hosts],
                       [b.weight for b in hosts])
    m.name_map[root.id] = "default"
    rep = make_replicated_rule(m, "rep")
    ec = make_erasure_rule(m, "ec", size=4)
    for numrep in (2, 3, 4):
        assert_match(m, rep, numrep, [0x10000] * 11)
    assert_match(m, ec, 4, [0x10000] * 11)
    assert_match(m, ec, 4, [0x10000] * 8 + [0, 0, 0])


def test_more_reps_than_hosts():
    # impossible placements: firstn returns short sets, indep holes
    m, rep, ec = build(6, 2, ec_size=6)   # only 3 hosts
    assert_match(m, rep, 5, [0x10000] * 6)
    assert_match(m, ec, 6, [0x10000] * 6)


def test_random_weight_fuzz():
    rng = np.random.default_rng(7)
    m, rep, ec = build(16, 2, ec_size=6)
    for _ in range(5):
        w = rng.choice([0, 0x3000, 0x8000, 0xC000, 0x10000],
                       size=16).tolist()
        xs = rng.integers(0, 2**31, 128).tolist()
        assert_match(m, rep, 3, w, xs)
        assert_match(m, ec, 6, w, xs)


def test_fallback_for_unsupported_shapes():
    # non-default tunables -> compile refuses, batch falls back to host
    m, rep, _ = build(8, 2)
    m.tunables.chooseleaf_stable = 0
    assert compile_rule(m, rep) is None
    assert_match(m, rep, 3, [0x10000] * 8)   # still correct via fallback


def test_batch_speedup_sanity():
    import time
    m, rep, _ = build(32, 4)
    w = [0x10000] * 32
    xs = list(range(4096))
    t0 = time.perf_counter()
    batch = batch_do_rule(m, rep, xs, 3, w)
    t_batch = time.perf_counter() - t0
    t0 = time.perf_counter()
    scalar = [do_rule(m, rep, x, 3, w) for x in xs[:256]]
    t_scalar = (time.perf_counter() - t0) * (len(xs) / 256)
    assert batch[:256] == scalar
    # vectorization must buy at least an order of magnitude
    assert t_batch < t_scalar / 10, (t_batch, t_scalar)


def test_jax_engine_matches_numpy():
    import numpy as np
    from ceph_tpu.ops.crush_kernel import (_straw2_draw,
                                           jax_straw2_winners)
    rng = np.random.default_rng(3)
    items = np.array([-2, -5, -9, -12, -13], np.int64)
    weights = rng.choice([0, 0x8000, 0x10000, 0x28000], 5).astype(np.int64)
    weights[0] = 0x10000
    xs = rng.integers(0, 2**31, 257)
    rs = np.arange(11, dtype=np.int64)
    got = jax_straw2_winners(items, weights, xs, rs)
    want = np.empty((257, 11), np.int64)
    for j, r in enumerate(rs):
        idx = _straw2_draw(items[None, :], weights[None, :], xs,
                           np.full(len(xs), r))
        want[:, j] = items[idx]
    assert np.array_equal(got, want)


def test_osdmap_batch_matches_scalar():
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from test_osdmap import build_map, mark_down
    m = build_map()
    mark_down(m, 3)
    from ceph_tpu.osd.osdmap import Incremental
    inc = Incremental(m.epoch + 1)
    inc.new_weight[7] = 0
    inc.new_primary_affinity[1] = 0x4000
    m.apply_incremental(inc)
    for pool in (1, 2):
        batch = m.map_pgs_batch(pool)
        for pg, up, upp, acting, actp in batch:
            assert (up, upp, acting, actp) == m.pg_to_up_acting_osds(pg)


def test_indep_numrep_exceeds_result_max_keeps_r_stride():
    """crush_do_rule splits out_size (slots: min(numrep, result_max))
    from numrep (the r stride: r = rep + numrep*ftotal, mapper.c:668).
    A 'chooseleaf indep 6' rule queried with result_max=4 must keep the
    6-stride retry sequence — conflating the two diverges from the
    scalar mapper whenever any retry fires."""
    m, _, ec = build(12, 2, ec_size=6)      # rule arg numrep = 6
    # degraded weights force retries so the stride actually matters
    for wname, wfn in WEIGHT_CASES:
        assert_match(m, ec, 4, wfn(12))
        assert_match(m, ec, 2, wfn(12))
