"""Share of the window the execute thread stood runnable and did not
run: wall less CPU seconds over its phases that do not block (every
phase but ``idle``, ``stream/wait``, ``window/fetch_wait``,
``sender/wait_host``, ``machine/fetch_wait``).  The thread marks its CPU
clock at the two ends of ``stream/wait`` — a system call, so twice a
window — and the work between two waits is charged as one, which is all
this sum needs.  Under three threads and one interpreter lock the share
is the lock held by the feed or the prefetch thread (or a machine that
ran something else).  The window's wall and CPU seconds by role and
phase go to standard error as one line: the prefetch and feed threads'
phases and CPU totals are in it."""

from benchlib import thread_account


def read(run):
    thread_account.log_by_role(run)
    return thread_account.runnable_not_running(run)
