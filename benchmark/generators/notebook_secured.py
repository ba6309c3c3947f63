"""``generators/notebook.py``'s requests, draw for draw, with each op named
``<op>_secured``: the same questions asked the same way, compared with the
reference that knows labels (``ops/*_secured.py``). The multiset a seed
draws is ``analyst-notebook``'s: the generator is handed the stream
untouched."""

from generators import notebook


def generate(params, rng, n, ctx):
    return [dict(req, op=req["op"] + "_secured") for req in notebook.generate(params, rng, n, ctx)]
