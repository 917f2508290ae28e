"""Programs the generation fleet compiled inside the window: new entries
in the servers' ``compiled_shapes`` (a program per distinct shape)."""


def read(records):
    c = records.get("counters") or {}
    return c.get("window_compiled_shapes")
