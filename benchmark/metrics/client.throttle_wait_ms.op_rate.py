"""Per-layer metric `client.throttle_wait_ms.op_rate`: tracer interval
client_throttle_wait (the Objecter's wait for its op budget, entry to
budget held, recorded only for an op that waited), mean ms per op
completed in the window; 0.0 when no op waited.  None under a program
whose tracer declares no such stage (it has no budget to wait for)."""

from benchmark import readers


def read(obs):
    try:
        from ceph_tpu.common.tracer import AUX_STAGES
    except ImportError:
        return None
    if "client_throttle_wait" not in AUX_STAGES or not obs.ops:
        return None
    return readers.stage_ms_per_op(
        obs, ["client_throttle_wait"]) or 0.0
