"""A kernel's share of its roofline: the least time the chip could take
for what the algorithm NEEDS in the traced window (``obs[need_flops]``,
``obs[need_bytes]``, counted by ``costs.py`` from the window's own shapes)
over the device time of the kernel's events, in percent."""

from chipbench import costs, xplane


def read(obs: dict, args: dict):
    tr = obs.get("trace")
    if not tr:
        return None
    flops = obs.get(args.get("need_flops", ""), 0.0) or 0.0
    nbytes = obs.get(args.get("need_bytes", ""), 0.0) or 0.0
    sec = xplane.op_seconds(tr, args["pattern"])
    if sec <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least, _ = costs.roofline_seconds(flops, nbytes, obs["peaks"])
    return 100.0 * least / sec
