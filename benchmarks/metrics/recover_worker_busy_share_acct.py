"""Share of the window in which the engines' recovery WORKER was inside
the native batch: ``sender/native`` on the accounts of role ``recover``
(one boundary pair a segment), wall seconds over the window.  The other
side of ``sender_wait_host_share_acct``, which is the replay thread
blocked on that worker's ``Future``: a wait that falls while this stays
is overlap gained; both falling is a faster ladder.  The window's wall
and CPU seconds by role and phase go to standard error as one line."""

from benchlib import thread_account


def read(run):
    thread_account.log_by_role(run)
    return thread_account.share(run, (thread_account.NATIVE,), "recover")
