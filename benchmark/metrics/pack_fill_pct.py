"""Real over padded tokens of the packer's micro-batches in the window."""


def read(records):
    c = records.get("counters") or {}
    if not c.get("pack_padded_tokens"):
        return None
    return 100.0 * c["pack_real_tokens"] / c["pack_padded_tokens"]
