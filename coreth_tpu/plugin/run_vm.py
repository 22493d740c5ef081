"""Standalone VM process: `python -m coreth_tpu.plugin.run_vm <socket>`.

The plugin/main.go role for multi-process tests: boots an empty VM,
serves it over the unix socket (rpcchainvm seam), and blocks until
killed.  The consensus side drives everything — including
`initialize` — over the socket.  The clock is synthetic (+10s per
read, like the VM test harnesses) so block building is deterministic
regardless of wall time.
"""

from __future__ import annotations

import itertools
import signal
import sys
import threading


def main(path: str, start_time: int = 1_000) -> None:
    from coreth_tpu import compile_cache
    from coreth_tpu.plugin import VM
    from coreth_tpu.plugin.service import serve

    compile_cache.configure()
    clock = itertools.count(start_time, 10).__next__
    vm = VM(clock=clock)
    server = serve(vm, path)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    print(f"vm serving on {path}", flush=True)
    stop.wait()
    server.close()


if __name__ == "__main__":
    main(sys.argv[1],
         int(sys.argv[2]) if len(sys.argv) > 2 else 1_000)
