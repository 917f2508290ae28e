"""Seconds of the window spent above each batch's median step time: the
stalls and slow steps, most of which ``train_tok_s_chip`` leaves out."""


def read(records):
    c = records.get("counters") or {}
    return c.get("slow_step_s")
