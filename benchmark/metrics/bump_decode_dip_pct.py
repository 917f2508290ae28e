"""1 - (tokens that arrived while a weight bump was in flight) / (what
the same run's bump-free rate gives for that time)."""


def read(records):
    h = records.get("bumps") or {}
    if not h.get("bump_s") or not h.get("free_s") or not h.get("free_tokens"):
        return None
    free_rate = h["free_tokens"] / h["free_s"]
    return 100.0 * (1.0 - h["bump_tokens"] / (free_rate * h["bump_s"]))
