"""Whole learner step's share of the chips' peak: the conv Q-network's
forward and backward FLOPs per update plus the target network's forward
(``bench/work.py``, from the shapes), times the traced window's updates
per second, over chips x the published bf16 peak.  The network runs in
float32, so the bf16 peak is an upper bound it cannot reach; the share
reads small and is printed with all its digits."""
from bench import work


def read(ctx):
    d = ctx.config["dqn"]
    h, w = ctx.config["law"]["frame_hw"]
    fwd = work.conv_qnet_forward_flops(
        h, w, d["history_len"], hidden=d["hidden"],
        n_actions=ctx.config["law"]["n_actions"])
    flops = work.qnet_update_flops(d["batch"], fwd,
                                   double=d.get("agent", "dqn") == "double")
    peak = work.peaks(ctx.device_kind)["bf16_flops"] * ctx.chips
    return 100.0 * flops * ctx.result["updates_per_s"] / peak
