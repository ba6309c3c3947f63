"""GDELT-shaped rows from a seed, for every attribute the configuration's
``schema`` lists (upstream's ``gdelt`` SimpleFeatureType, geomesa-tools).

Row i has feature id i and ids are in arrival order, as GDELT's
GlobalEventID is: the times are n uniform times over the span in
ascending order (the order statistics of a uniform draw, made as a
normalised running sum of exponential gaps), so the reference cuts a time
window with two binary searches. Points are ``harness.data.gdelt_points``
(half uniform, half in 64 Gaussian clusters). The other attributes are
synthetic with GDELT's shapes: CAMEO-like codes of 2 to 4 characters,
3-letter country, group, ethnic and religion codes, actor names of up to
24 characters, all drawn with a skew from fixed vocabularies whose first
entry is the empty string (GDELT leaves most actor fields empty); small
integer counts; Goldstein scale and tone as doubles. They live on the
host in the program's column store and travel in every answer.

``make(config, n, seed)`` returns the ``Columns`` the store is loaded
from and the reference reads. An attribute this module has no rule for is
an error: a configuration with another schema brings its own data set.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import lru_cache

import numpy as np

from harness.data import DAY_MS, cluster_centres, gdelt_points, sub_rng

CHUNK_ROWS = 1 << 18

_WORDS = ["", "GOVERNMENT", "POLICE", "UNITED STATES", "MINISTRY", "PRESIDENT", "REBEL",
          "COMPANY", "STUDENT", "ARMY", "COURT", "MEDIA", "SCHOOL", "RUSSIA", "CHINA",
          "PROTESTER", "SENATE", "BUSINESS", "MILITANT", "VILLAGE", "HOSPITAL", "UNION",
          "CITIZEN", "EMPLOYEE", "LAWMAKER", "CRIMINAL", "REFUGEE", "FARMER", "JUDGE",
          "PRISON", "NAVY", "AIR FORCE"]


@lru_cache(None)
def _codes(width: int, size: int) -> np.ndarray:
    """A vocabulary of ``size`` codes of ``width`` characters, "" first."""
    digits = "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
    out = [""]
    for i in range(1, size):
        s, v = "", i * 7919
        for _ in range(width):
            s += digits[v % 36]
            v //= 36
        out.append(s)
    return np.array(out, dtype=f"<U{width}")


@lru_cache(None)
def _names() -> np.ndarray:
    out = sorted({(a + " " + b).strip() for a in _WORDS for b in _WORDS}, key=len)
    return np.array(out, dtype="<U24")


def _skewed(rng, vocab, n):
    """Entries of ``vocab`` by a squared-uniform rank: the first few carry
    most of the rows, as a handful of codes does in GDELT."""
    return vocab[(len(vocab) * rng.random(n) ** 2).astype(np.int32)]


def _small_counts(rng, n):
    return np.minimum(rng.geometric(0.2, n), 500).astype(np.int32)


#: how each attribute of upstream's ``gdelt`` type is drawn
RULES = {
    "globalEventId": lambda rng, n, lo: np.arange(1_000_000_000 + lo, 1_000_000_000 + lo + n)
    .astype("<U10"),
    "eventCode": lambda rng, n, lo: _skewed(rng, _codes(4, 300), n),
    "eventBaseCode": lambda rng, n, lo: _skewed(rng, _codes(3, 150), n),
    "eventRootCode": lambda rng, n, lo: _skewed(rng, _codes(2, 21), n),
    "isRootEvent": lambda rng, n, lo: rng.integers(0, 2, n, dtype=np.int32),
    "quadClass": lambda rng, n, lo: rng.integers(1, 5, n, dtype=np.int32),
    "goldsteinScale": lambda rng, n, lo: np.round(rng.uniform(-10, 10, n), 1),
    "numMentions": lambda rng, n, lo: _small_counts(rng, n),
    "numSources": lambda rng, n, lo: _small_counts(rng, n),
    "numArticles": lambda rng, n, lo: _small_counts(rng, n),
    "avgTone": lambda rng, n, lo: rng.normal(-2.0, 4.0, n),
}
for _actor in ("actor1", "actor2"):
    RULES[_actor + "Name"] = lambda rng, n, lo: _skewed(rng, _names(), n)
    RULES[_actor + "Code"] = lambda rng, n, lo: _skewed(rng, _codes(6, 2000), n)
    RULES[_actor + "CountryCode"] = lambda rng, n, lo: _skewed(rng, _codes(3, 250), n)
    RULES[_actor + "GroupCode"] = lambda rng, n, lo: _skewed(rng, _codes(3, 60), n)
    RULES[_actor + "EthnicCode"] = lambda rng, n, lo: _skewed(rng, _codes(3, 120), n)
    RULES[_actor + "Religion1Code"] = lambda rng, n, lo: _skewed(rng, _codes(3, 30), n)
    RULES[_actor + "Religion2Code"] = lambda rng, n, lo: _skewed(rng, _codes(3, 30), n)


def parse_schema(spec: str):
    """[(name, type)] and the names of the date and the default geometry,
    from a GeoMesa spec string ("a:String,dtg:Date,*geom:Point:srid=4326")."""
    attrs, dtg, geom = [], None, None
    for part in spec.split(";")[0].split(","):
        name, kind = part.split(":")[:2]
        if name.startswith("*"):
            name = name[1:]
            geom = name
        if kind == "Date" and dtg is None:
            dtg = name
        attrs.append((name, kind))
    return attrs, dtg, geom


class Columns:
    """The generator's columns: the reference's whole input, owned by the
    benchmark. ``x``, ``y``, ``t`` (ascending epoch millis) and ``attrs``
    {name: column} of the other attributes. Rows are drawn in chunks of
    CHUNK_ROWS, each from its own stream of the seed and written in place,
    on a few threads (NumPy's generators release the interpreter lock)."""

    def __init__(self, config: dict, n: int, seed: int):
        self.schema, self.dtg, self.geom = parse_schema(config["schema"])
        names = [a for a, _ in self.schema if a not in (self.dtg, self.geom)]
        missing = [a for a in names if a not in RULES]
        if missing:
            raise KeyError(f"datagen/gdelt.py has no rule for the attributes {missing}")
        self.t0 = int(np.datetime64(config["data"]["t0"], "ms").astype(np.int64))
        self.span_ms = int(config["span_days"]) * DAY_MS
        self.cx, self.cy = cluster_centres(sub_rng(seed, 0))
        self.x, self.y = np.empty(n), np.empty(n)
        gaps = np.ones(n + 1)
        probe = np.random.default_rng(0)
        self.attrs = {a: np.empty(n, RULES[a](probe, 1, 0).dtype) for a in names}

        def chunk(job):
            c, lo = job
            hi = min(lo + CHUNK_ROWS, n)
            rng = np.random.default_rng([int(seed), 0, int(c)])
            self.x[lo:hi], self.y[lo:hi] = gdelt_points(hi - lo, rng, self.cx, self.cy)
            gaps[lo:hi] = rng.standard_exponential(hi - lo)
            for a in names:
                self.attrs[a][lo:hi] = RULES[a](rng, hi - lo, lo)

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(chunk, enumerate(range(0, n, CHUNK_ROWS))))
        # ascending times: the running sum of the gaps, scaled to the span
        np.cumsum(gaps, out=gaps)
        t = (gaps[:-1] * (self.span_ms / gaps[-1])).astype(np.int64)
        np.minimum(t, self.span_ms - 1, out=t)
        self.t = self.t0 + t

    def __len__(self) -> int:
        return len(self.x)

    def context(self) -> dict:
        """What a request generator may know of the data."""
        return {"cx": [float(v) for v in self.cx], "cy": [float(v) for v in self.cy],
                "t0": self.t0, "span_ms": self.span_ms, "n_rows": len(self)}

    def row(self, i: int) -> dict:
        """Row i in the form every answer's witness row is brought to:
        the date as epoch millis, the point as [x, y], the rest as Python
        values."""
        out = {self.dtg: int(self.t[i]), self.geom: [float(self.x[i]), float(self.y[i])]}
        out.update({a: c[i].item() for a, c in self.attrs.items()})
        return out


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
