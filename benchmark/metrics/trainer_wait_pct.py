"""Share of the master's step time in which no trainer MFC ran:
1 - sum(timeperf/<mfc>) / timeperf/e2e over the window's steps."""


def read(records):
    steps = records.get("master_steps") or []
    e2e = sum(s.get("timeperf/e2e", 0.0) for s in steps)
    if not e2e:
        return None
    busy = sum(v for s in steps for k, v in s.items()
               if k.startswith("timeperf/")
               and k not in ("timeperf/e2e", "timeperf/mfu"))
    return 100.0 * (1.0 - busy / e2e)
