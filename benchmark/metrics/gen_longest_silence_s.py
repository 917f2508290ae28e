"""The longest time inside the window in which no reply reached the
client: a compile, a hang or a weight swap that stops every row."""


def read(records):
    c = records.get("counters") or {}
    return c.get("longest_silence_s")
