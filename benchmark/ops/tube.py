"""Op ``tube``: one ``geomesa_tpu.process.tube_select`` along the track
``track_xy`` ([n, 2] f64) at ``track_t`` ([n] epoch millis, ascending; both
arrays: a track of 360 waypoints crosses no process boundary here) with
``buffer_m``; ``bin_ms`` and
``max_bins`` are the program's defaults unless the request names them
(the warm ladder does). Embedded only, as ``ops/knn.py``.

``compare`` holds the answer's id set to ``harness/reference_process.tube``
over all rows, counts ids answered twice, and checks the witness row."""

import numpy as np

from harness import reference_process as ref
from harness import requests as rq
from ops.knn import compare_witness


def embedded(store, req):
    from geomesa_tpu.process import tube_select

    more = {k: int(req[k]) for k in ("bin_ms", "max_bins") if req.get(k) is not None}
    return rq.collection_answer(tube_select(
        store.ds, store.type_name, req["track_xy"], req["track_t"], float(req["buffer_m"]),
        **more))


def members(req) -> int:
    return 1


def size(answer) -> int:
    return len(answer["ids"])


def compare(tally, cols, req, answer) -> None:
    want = ref.tube(cols, req["track_xy"], req["track_t"], req["buffer_m"])
    tally["rows_compared"] += len(want)
    got = np.sort(np.asarray(answer["ids"], np.int64))
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    tally["wrong_answers"] += int(not np.array_equal(got, want))
    compare_witness(tally, cols, answer)
