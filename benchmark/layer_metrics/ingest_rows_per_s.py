"""Streaming tier, host clock: rows of the batches whose sound
acknowledgement was wholly read inside the window, a second of window.
Beside the traffic file's ``rows_per_s`` it says whether the pace held."""


def read(view):
    return view["client"].get("ingest_rows_per_s")
