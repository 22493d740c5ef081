"""Share of the signatures whose recovery completed on the device
ladder rather than in the native host batch."""


def read(run):
    dev = sum(r["sigs_device"] for r in run["passes"])
    host = sum(r["sigs_host"] for r in run["passes"])
    return 100.0 * dev / (dev + host) if dev + host else None
