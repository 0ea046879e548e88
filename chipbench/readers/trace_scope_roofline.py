"""A kernel's share of its roofline, the kernel found by the PROGRAM'S
name: ``trace_roofline``'s arithmetic (the least time the chip could take
for what the algorithm NEEDS in the traced window, ``obs[need_flops]`` and
``obs[need_bytes]``, over the device time) with ``trace_scope_per``'s
device time — the self time of the operations whose ``op_name`` holds
``scope`` (and ``within``, if given).  ``{"scope": "hvd_moe_experts",
"within": "_tick", "need_flops": "moe_need_flops", "need_bytes":
"moe_need_bytes"}``.  Nothing to read (no trace, no operation of that
name, no need counted) reads as None."""

from chipbench import costs
from chipbench.readers import trace_scope_per


def read(obs: dict, args: dict):
    tr = trace_scope_per.parsed(obs)
    if tr is None:
        return None
    flops = obs.get(args.get("need_flops", ""), 0.0) or 0.0
    nbytes = obs.get(args.get("need_bytes", ""), 0.0) or 0.0
    sec = trace_scope_per.seconds_under(
        tr["self"], args["scope"], trace_scope_per.known_scopes(),
        args.get("within"))
    if sec <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least, _ = costs.roofline_seconds(flops, nbytes, obs["peaks"])
    return 100.0 * least / sec
