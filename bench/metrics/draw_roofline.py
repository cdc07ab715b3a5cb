"""The draw's share of its memory roofline: the least bytes the AMPER-fr
algorithm moves per draw (one read of the quantized table and validity,
then the drawn rows; ``bench/work.py``, independent of the implementation)
over the draw program's device time x the published HBM bandwidth."""
from bench import work
from bench.metrics._program_ms import mean_ms


def read(ctx):
    ms = mean_ms(ctx, "replay_draw")
    if not ms:
        return None
    d = ctx.config["dqn"]
    h, w = ctx.config["law"]["frame_hw"]
    row = work.gathered_row_bytes(h * w, d["history_len"], d["n_step"])
    nbytes = work.amper_fr_draw_bytes(d["replay_size"], d["amper_m"],
                                      d["batch"], row_bytes=row)
    bw = work.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * nbytes / (ms * 1e-3 * bw)
