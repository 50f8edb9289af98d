"""compile cache: programs built during set-up (``backend_compiles`` of
``compile.LEDGER`` when the window starts), compiled or read from the
persistent cache alike."""


def read(data):
    return data["counters"]["setup_backend_compiles"]
