"""Entry points: median of the whole ``encode`` spans of the ``http``
roots: the GeoJSON / Arrow encoding of the answer, chunk by chunk, and
the writes of the chunks to the socket between them."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "encode", roots=("http",), whole=True)
