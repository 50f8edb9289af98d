"""compile cache: programs built (compiled or read from the persistent
cache) between the first and the last sync of the window, from
``compile.LEDGER``'s ``backend_compiles``.  Anything but 0 also makes
the run incorrect."""


def read(data):
    return data["counters"]["compiles_in_window"]
