"""Share of the window in which the device had nothing in flight, by the
program's own count of dispatches issued and read back, over the WHOLE
window (``device_idle_share`` sees 2.5 s of one pass).  A lower bound on
idle: completion is seen only when the host reads back.  The seconds by
the phase the replay thread was in go to standard error as one line."""

import json
import sys

from benchlib.account import starved


def read(run):
    found = starved(run)
    if found is None:
        return None
    print(json.dumps({"device_starved": found}), file=sys.stderr,
          flush=True)
    return found["share"]
