"""Share of the window the execute thread of a streamed pass spent
waiting for blocks (``stream/wait``: the pipeline's top-up of a window
from its queue).  At a fixed offered rate this is the thread's slack:
it falls as the work a block costs rises, and reaches 0 at capacity.
Before the phase existed these seconds read as
``replay_loop_share_acct``."""

from benchlib import thread_account


def read(run):
    return thread_account.share(run, ("stream/wait",), "replay")
