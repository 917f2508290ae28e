"""6*N*T over window, chips and the chip's bf16 peak: an end-to-end
utilisation (recompute not counted), not a roofline share. T over window
is ``mean_tok_s``: every step of the window counts, nothing taken out."""

from benchmark import peaks


def read(records):
    c = records.get("counters") or {}
    if not c.get("mean_tok_s"):
        return None
    flops_s = peaks.train_flops_6nt(c["n_params"], c["mean_tok_s"])
    peak = peaks.peak(records["device"]["kind"])["flops_bf16"]
    return 100.0 * flops_s / records["chips"] / peak
