"""Whole learner step's share of the chips' peak: the FLOPs of one update
of the network the configuration's learner reference checks
(``update_flops`` of ``law.reference_learner``, from the shapes), times
the traced window's updates per second, over chips x the published bf16
peak.  The network runs in float32, so the bf16 peak is an upper bound it
cannot reach; the share reads small and is printed with all its digits."""
from bench import work


def read(ctx):
    ref = ctx.reference(ctx.config["law"]["reference_learner"])
    peak = work.peaks(ctx.device_kind)["bf16_flops"] * ctx.chips
    return 100.0 * ref.update_flops(ctx.config) \
        * ctx.result["updates_per_s"] / peak
