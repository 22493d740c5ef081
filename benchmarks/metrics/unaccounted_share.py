"""What the accounted shares leave of the window: 100 minus every
per-layer metric of the cell whose reader says ``WINDOW_SHARE = True``
(today decode, engine build, sender, classify, device wait and trie).
Engine teardown between passes, the lead block's own path and
everything ``ReplayStats`` has no field for land here (ROADMAP D16).
A later share that is added as a file is subtracted without an edit
here."""

from benchlib import names


def read(run):
    if not run["passes"] or run["window_s"] <= 0:
        return None
    total = 0.0
    for m in names.cell_metrics(run["spec"], "per_layer",
                                run["cell"]["name"]):
        reader, _ = names.load_named("metrics", m["name"])
        if getattr(reader, "WINDOW_SHARE", False):
            part = reader.read(run)
            if part is None:
                return None
            total += part
    return 100.0 - total
