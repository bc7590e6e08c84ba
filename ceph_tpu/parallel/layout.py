"""Device-mesh layout for the distributed data plane.

The reference moves erasure-coded shards between OSD processes over its
Messenger (src/osd/ECBackend.cc fan-out of MOSDECSubOpWrite; src/msg/ NCC-less
custom transport).  The TPU-native equivalent for co-located OSD shards is a
jax device mesh:

  * axis "host"  — data parallelism over independent stripes/PGs (the
    reference's "objects hash to PGs" axis, OSDMap.cc:1470)
  * axis "shard" — the byte dimension of a stripe, striped across devices
    (the reference's Striper/ECUtil stripe axis, osdc/Striper.h:31)

Collectives ride ICI: parity fan-out is a ppermute ring (the
MOSDECSubOpWrite hop), scrub aggregation is a psum (the PGMap stat roll-up).
This module is used by __graft_entry__.dryrun_multichip; the live OSD
device-mesh execution mode (osd_mesh_mode=on) lives in
ceph_tpu/parallel/mesh_exec.py, which runs the same all_gather/row-sharded
encode INSIDE the EC write path and hands shard bytes to co-located OSDs
in process (tests/test_mesh_mode.py boots it end to end).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_devices: Optional[int] = None,
              axes: Sequence[str] = ("host", "shard")) -> Mesh:
    """Mesh over the first n devices: 'host' x 'shard', shard innermost so
    the stripe axis rides the fastest ICI links."""
    devs = jax.devices()
    n = n_devices or len(devs)
    devs = devs[:n]
    shard = 1
    for cand in (8, 4, 2, 1):
        if n % cand == 0 and cand <= n:
            shard = cand
            break
    grid = np.empty(n, dtype=object)   # plain np.array misparses devices
    grid[:] = devs
    return Mesh(grid.reshape(n // shard, shard), axes)


def ec_cluster_step(mesh: Mesh, bitmat: jnp.ndarray):
    """Build the jitted multi-chip EC data-plane step.

    Input  data [B, k, L]: B stripes over 'host', bytes L over 'shard'.
    Per step: encode parity (MXU matmul), ring-shift parity one position
    along 'shard' (the shard fan-out hop), and psum a per-chunk crc-proxy
    over 'host' (the scrub roll-up).  Returns (parity, scrub) with parity
    laid out like the data.
    """
    from ceph_tpu.ec.kernel import _apply_bitmatrix

    def step(data):
        parity = jax.vmap(lambda d: _apply_bitmatrix(bitmat, d))(data)
        # shard fan-out hop: each device hands its parity slice to the next
        # ring position (ECBackend's MOSDECSubOpWrite to the next shard OSD)
        n_shard = mesh.shape["shard"]
        perm = [(i, (i + 1) % n_shard) for i in range(n_shard)]
        parity = jax.lax.ppermute(parity, "shard", perm)
        # scrub roll-up: per-chunk byte-sum aggregated across hosts + shards
        local_sum = jnp.sum(parity.astype(jnp.uint32), axis=(0, 2))
        scrub = jax.lax.psum(jax.lax.psum(local_sum, "host"), "shard")
        return parity, scrub

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P("host", None, "shard"),),
        out_specs=(P("host", None, "shard"), P()),
        check_vma=False)
    return jax.jit(sharded)


def ec_recover_step(mesh: Mesh, dec_bitmat: jnp.ndarray,
                    n_surv: int):
    """Build the jitted multi-chip EC RECOVERY step — the data-plane
    analog of ECBackend::continue_recovery_op (osd/ECBackend.cc:484):
    the primary gathers k survivor shards (MOSDECSubOpRead fan-in) and
    decodes the lost chunks.

    Mesh layout is the OSD placement itself: each 'shard' position
    holds ITS OWN chunk of every stripe — input surv [B, n_surv, L]
    sharded (host, shard, -): the chunk AXIS is distributed, so no
    device can decode alone.  The step all_gathers the survivor chunks
    along 'shard' (the ICI ride replacing k point-to-point shard
    reads) and every device runs the decode matmul locally — the
    rebuilt chunks are then immediately available at every shard
    position (replicate-on-recover), and a psum over 'host' rolls up
    a scrub digest of the reconstruction.

    Requires n_surv % mesh.shape['shard'] == 0 (each device holds an
    equal slice of the survivor set).
    """
    assert n_surv % mesh.shape["shard"] == 0, \
        (n_surv, dict(mesh.shape))
    from ceph_tpu.ec.kernel import _apply_bitmatrix

    def step(surv):
        # surv local block: [B_local, n_surv/n_shard, L] — gather the
        # full survivor set along the shard axis (MOSDECSubOpRead)
        full = jax.lax.all_gather(surv, "shard", axis=1, tiled=True)
        lost = jax.vmap(lambda d: _apply_bitmatrix(dec_bitmat, d))(full)
        local_sum = jnp.sum(lost.astype(jnp.uint32), axis=(0, 2))
        scrub = jax.lax.psum(local_sum, "host")
        return lost, scrub

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P("host", "shard", None),),
        out_specs=(P("host", None, None), P()),
        check_vma=False)
    return jax.jit(sharded)


def replicated(mesh: Mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P()))


def host_sharded(mesh: Mesh, x, spec: P):
    return jax.device_put(x, NamedSharding(mesh, spec))
