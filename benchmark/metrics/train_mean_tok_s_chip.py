"""The batches' tokens over the sum of every batch's mean step time, per
chip: every step of the window counts and nothing is taken out.
``train_tok_s_chip`` leaves each batch's one slowest visit out, so a stall
that comes once a window shows here and in ``train_slow_step_s`` and not
there."""


def read(records):
    c = records.get("counters") or {}
    if not c.get("mean_tok_s"):
        return None
    return c["mean_tok_s"] / records["chips"]
