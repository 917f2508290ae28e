"""Tokens the servers prefilled in the window over the prompt tokens of
the requests started in it: 1.0 = every prompt prefilled once per sample,
1/group_size = once per group."""


def read(records):
    c = records.get("counters") or {}
    if not c.get("prompt_tokens_started"):
        return None
    return c["prefill_tokens"] / c["prompt_tokens_started"]
