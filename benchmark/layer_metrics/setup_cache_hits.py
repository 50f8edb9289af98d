"""compile cache: programs of set-up served from the persistent cache
(``persistent_hits`` of ``compile.LEDGER`` when the window starts).  0
in a checkout's first run, and it cannot be reported as 0."""


def read(data):
    return data["counters"]["setup_persistent_hits"] or None
