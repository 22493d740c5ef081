"""Share of the window the REPLAY thread spent inside the native batch
itself (``sender/native`` on the engines' own accounts): where
``warm_senders`` runs on the calling thread — one signature a block on
the consensus path — this is the call, and ``sender_pack_share_acct``
beside it the packing and applying alone."""

from benchlib import thread_account


def read(run):
    return thread_account.share(run, (thread_account.NATIVE,), "replay")
