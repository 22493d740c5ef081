"""Microseconds of the native batch a signature, at the batch sizes the
cell feeds it: ``sender/native`` wall over the accounts of EVERY role
of the window's passes (the recovery worker's segments, the replay
thread's ``warm_senders``, the serve prefetcher's chunks) over the
signatures those batches recovered (``sigs_host`` of the pass rows).
A hand probe on one machine read 9.3 at 3,565 signatures a batch, 110
at 16, 338 at 1 (PERF.md)."""

from benchlib import thread_account


def read(run):
    accounts = thread_account.window_accounts(run)
    sigs = sum(r.get("sigs_host", 0) for r in run["passes"])
    if accounts is None or not sigs:
        return None
    return 1e6 * thread_account.seconds(
        accounts, (thread_account.NATIVE,)) / sigs
