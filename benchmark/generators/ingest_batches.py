"""A feed's writer: batch after batch of new events for ``POST /ingest``.

Parameters (the traffic file's ``params``): ``batch_rows`` (features a
batch), ``writers`` (the writers that share the schedule: they take the
batch ids in turn). A request carries the batch's key
(``datagen/gdelt_live.batch_spec``), not its rows: the writer's process
makes the body from it, the parent makes the columns again for the check.
``ctx["client_index"]`` is the writer's number.
"""

from datagen import gdelt_live


def generate(params, rng, n, ctx):
    w, writers, rows = int(ctx["client_index"]), int(params["writers"]), int(params["batch_rows"])
    return [{"op": "ingest", "klass": "batch", "fmt": "geojson",
             "spec": gdelt_live.batch_spec(ctx, w, k, writers, rows)} for k in range(n)]
