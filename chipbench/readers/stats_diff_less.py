"""``stats_diff`` with a subtrahend: the growth of ``num`` LESS the growth of
``less``, over the growth of ``den``, all from the program's own ``/stats``
snapshots.  ``{"num": ["prefill_padded_tokens_total"], "less":
["prefill_tokens_total"], "den": ["prefill_padded_tokens_total"], "scale":
100}`` is the padding's share of what the prefill executables ran, from the
two counters the program keeps — it need not publish their difference."""

from chipbench.readers.stats_diff import _grown


def read(obs: dict, args: dict):
    s0, s1 = obs.get("stats0"), obs.get("stats1")
    if not s0 or not s1:
        return None
    den = _grown(s0, s1, args["den"])
    if den <= 0:
        return None
    num = _grown(s0, s1, args["num"]) - _grown(s0, s1, args["less"])
    return args.get("scale", 1.0) * num / den
