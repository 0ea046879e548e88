"""Model FLOP/s utilisation of a training window: required FLOPs per
token (``costs.train_flops_per_token``: no recomputation counted) x tokens
per second over chips x the chip's bf16 peak, in percent."""

from chipbench import costs


def read(obs: dict, args: dict):
    if not obs.get("window_s") or not obs.get("train_tokens"):
        return None
    need = costs.train_flops_per_token(obs["dims"], obs["seq"])
    rate = obs["train_tokens"] / obs["window_s"]
    peak = obs["chips"] * obs["peaks"]["bf16_flops_per_s"]
    return 100.0 * need * rate / peak
