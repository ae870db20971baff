"""Mean milliseconds of one checkpoint capture in SET-UP: span
`checkpoint_capture` (`txtrace.stage.checkpoint_capture`) in the snapshot
taken at window open, so the captures the set-up crossed and none of the
read-back's.  The serving thread is held for all of it (children
`checkpoint_d2h`: every table column to the host; `checkpoint_digest`), and
so is every session: it moves `setup_s`.  None where no capture fell in
set-up, or the program has no such span."""


def read(run):
    spans = run["snapshots"]["open"].get("histograms", {})
    captures = spans.get("txtrace.stage.checkpoint_capture")
    if not captures or not captures.get("count"):
        return None
    return captures["sum"] / captures["count"] / 1e3
