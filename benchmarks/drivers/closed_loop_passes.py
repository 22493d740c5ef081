"""Closed loop, one client, backlog: whole passes until time is up.

The window is contiguous: it opens when the first pass starts and
closes when the pass that is in flight at ``seconds`` has finished.
Whatever happens between passes (tearing down the last engine, garbage
collection) is inside it.

A driver gets one context and gives back the window.  ``ctx`` holds
``genesis``, ``wire`` (the chain's blocks as wire bytes),
``engine_kw`` (the configuration's engine arguments) and ``pass_fn``
(one catch-up pass over the whole chain on a fresh engine, as
``benchlib.replay_pass.one_pass`` runs it; set-up has warmed exactly
this call).  It returns ``t_open``, ``t_close`` and ``rows``: one row
for every replay from the genesis state, in ``one_pass``'s keys (the
harness holds each row's ``root`` to the chain's last root and reads
the state back from the last row's engine).  A driver whose cell has an
end-to-end metric of its own puts it under ``values``, by name.
"""

import time


def drive(ctx: dict, seconds: float, traffic: dict) -> dict:
    rows = []
    t_open = time.monotonic()
    while True:
        rows.append(ctx["pass_fn"]())
        if time.monotonic() - t_open >= seconds:
            break
    return {"t_open": t_open, "t_close": time.monotonic(), "rows": rows}
