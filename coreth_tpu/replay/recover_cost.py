"""When a batch of signatures is done on each of the two batch engines.

Sender recovery has two batch engines: the device ECDSA ladder
(crypto/secp_device) and the native C++ batch (crypto/native).  Every
routing decision — which engine a ``_SenderPipeline`` segment goes to,
how ``_recover_packed`` divides one synchronous batch — reads the ONE
cost function here, and asks it one thing: which engine has this batch
done first.  Its parameters are the batch size, ``os.cpu_count()`` and
a table measured on the chip; no environment variable.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Mapping, Optional

from coreth_tpu.crypto.secp_device import MAX_CHUNK, _pad_pow2


@dataclass(frozen=True)
class RecoverCost:
    """The ladder is latency-bound: a launch costs about the same
    whatever its pow2 bucket holds, so its cost is a TABLE of seconds a
    warm launch (host prep, kernel, read-back, host finish) by bucket.
    The native batch stripes over the hardware threads once it has 16
    signatures for each (native/secp256k1.cc coreth_ecrecover_batch);
    below that it runs on the calling thread."""
    launch_s: Mapping[int, float]   # pow2 bucket -> seconds a launch
    host_fixed_s: float             # spawning and joining the threads
    host_sig_core_s: float          # seconds a signature on ONE core
    cores: Optional[int] = None     # None: os.cpu_count() at the call

    def ladder_s(self, n: int) -> float:
        """Seconds for the launches ``n`` signatures need."""
        # a bucket the table does not have is one the model cannot
        # tell: it never wins
        full, rest = divmod(n, MAX_CHUNK)
        return sum(self.launch_s.get(_pad_pow2(k), float("inf"))
                   for k in [MAX_CHUNK] * full + [rest] * (rest > 0))

    def host_s(self, n: int) -> float:
        """Seconds for the native batch over ``n`` signatures."""
        if n <= 0:
            return 0.0
        cores = self.cores or os.cpu_count() or 1
        if cores < 2 or n < 16 * cores:
            return n * self.host_sig_core_s
        return self.host_fixed_s + n * self.host_sig_core_s / cores

    def split(self, n: int) -> int:
        """How many of ``n`` signatures the ladder takes when both
        engines start together and the batch is done when the later one
        is: the count that is done first.  A launch costs its bucket
        whatever it holds, so only full buckets (and all of ``n``) are
        worth asking.  Ties go to the host: 0 where no launch beats the
        native batch over the whole of ``n``."""
        best, best_s = 0, self.host_s(n)
        for base in range(0, n, MAX_CHUNK):
            for bucket in self.launch_s:
                n_dev = min(base + bucket, n)
                s = max(self.ladder_s(n_dev), self.host_s(n - n_dev))
                if s < best_s:
                    best, best_s = n_dev, s
        return best


# Measured warm on one TPU v5 lite and its host's 13 cores (PR 32,
# chip_smoke.py --phase recover; the runs are in PERF.md section 3).
# launch_s: median of three recover_addresses_device calls filling the
# bucket.  The native batch as it reads beside a live jax process,
# which is where the engine runs it: 0.0098 ms a signature over 13
# threads plus 5 ms to spawn and join them (alone in a process: 0.0081
# and 3 ms); 0.10 ms a signature on the calling thread below 16 a core.
MEASURED = RecoverCost(
    launch_s={64: 0.127, 128: 0.129, 256: 0.148, 512: 0.175,
              1024: 0.235, 2048: 0.277, 4096: 0.297},
    host_fixed_s=0.005, host_sig_core_s=0.000127)
