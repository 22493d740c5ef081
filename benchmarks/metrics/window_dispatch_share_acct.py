"""Share of the window spent uploading a transfer window's inputs and
dispatching it (``window/upload`` + ``window/dispatch``: the
``jnp.asarray``s, the jitted call, ``copy_to_host_async``).  Self time
from the engine's account."""

from benchlib.account import share


def read(run):
    return share(run, ("window/upload", "window/dispatch"))
