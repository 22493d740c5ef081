"""Share of the window that was the replay thread's own WORK in sender
recovery: packing signatures and submitting to the native pool
(``sender/pack``), host prep and dispatch of a device batch
(``sender/issue_device``), the host finish and priming the sender caches
(``sender/apply``).  Self time from the engine's account."""

from benchlib.account import share


def read(run):
    return share(run, ("sender/pack", "sender/issue_device", "sender/apply"))
