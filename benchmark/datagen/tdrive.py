"""Taxi GPS reports shaped like the T-Drive trajectory sample (Microsoft
Research Asia: 10,357 Beijing taxis, 2 to 8 February 2008, one file a taxi,
lines of ``taxi id, date time, longitude, latitude``), for upstream's
``tdrive`` type as far as it is recalled
(``taxiId:String:index=true,dtg:Date,*geom:Point:srid=4326``; the
configuration's ``about.assumed`` lists what is a guess). All from a seed;
nothing is read from the release (no network).

**The city** is the deployment's: drawn from the configuration's
``data.city_seed`` and NOT from the run's seed, so every seed drives the
same streets between the same places (``datagen/ais.py`` has why). A street
grid of ``PITCH_DEG`` (some 430 m) over ``OUTER``; ``N_HOT`` hot spots on
its crossings, ``URBAN_SHARE`` of them N(``CENTRE``, ``URBAN_SIGMA``) inside
``RING`` and the rest uniform over ``OUTER`` less a margin, with
Zipf(``HOT_ZIPF``) weights by a drawn rank.

**The fleet**, taxi ids the decimal strings ``"1"`` .. ``"<taxis>"``. Every
taxi has

- its own **sampling interval**, lognormal (``INTERVAL_SIGMA``) clipped to
  ``INTERVAL_S`` seconds: the release's seconds-to-ten-minutes spread, so a
  few taxis hold many times the median's rows (a value's row span in the
  attribute index differs by that much);
- ``SHIFTS`` **shifts**, one in each twelve hours of the week, of unequal
  length, ``DUTY`` of the week in all (16.8M reports x 177 s / 10,357 taxis
  / 168 h), a tenth more or less by taxi;
- a chain of **trips** between hot spots drawn by weight, each along the
  grid (one leg east-west, one north-south, in either order) at one speed
  of ``SPEED_KMH``, then a stop of ``STOP_S`` seconds at its end (a fare
  sought, a rank, a meal): 0-60 km/h with stops. The chain runs on the
  taxi's duty clock; a shift's end is not a trip's.

A taxi's reports are spaced its interval apart on its duty clock (a phase
and a jitter of three tenths of the interval, whole seconds, never two in
one second), each the taxi's place then plus N(0, ``GPS_SIGMA_M``) on each
axis: f64 and free. The rows a taxi gets are its share of ``n`` by duty
over interval, to the row, so the fleet's mean interval is ``DUTY`` x a
week x taxis / n: 175.5 s at the configuration's size. One report in
``JUNK_ONE_IN`` is mislocated, uniform over lon +-180, lat +-80 (the
release holds such fixes; they stretch the statistics' envelope).

Rows come taxi by taxi in time order, as the release's files do; row i has
feature id i. ``Columns.taxi`` is each row's taxi NUMBER (its id as an
integer), ``first`` / ``rows`` where a taxi's rows start and how many they
are, ``attrs["taxiId"]`` the strings the store is loaded with.

**Smaller than the configuration** (rehearsals, tests): the same week with
proportionally fewer taxis, so a track keeps its length; never under
``MIN_FLEET`` taxis, below which the tracks thin instead.

The module imports ``ops.query_attr`` for one reason: that op brings a
comparison of its own (an ordered answer's id sequence) with its key in
``harness.check.LIMITS``, and a run's tally is made from ``LIMITS`` after
the data set's module is loaded and before any request's op is.
"""

from __future__ import annotations

import numpy as np

import ops.query_attr  # noqa: F401  (its key in check.LIMITS before the run's tally: see above)
from datagen.ais import seg_cumsum
from datagen.gdelt import parse_schema

OUTER = (116.0, 39.6, 116.8, 40.3)  # lon0, lat0, lon1, lat1: every true fix
RING = (116.20, 39.75, 116.55, 40.03)  # nine tenths of them
CENTRE = (116.375, 39.89)
PITCH_DEG = (0.005, 0.004)  # the street grid: 427 m x 445 m at this latitude
N_HOT, HOT_ZIPF = 512, 1.1
URBAN_SHARE, URBAN_SIGMA = 0.9, (0.095, 0.076)
MARGIN_DEG = 0.02  # hot spots keep this far inside OUTER (GPS noise stays inside)
DUTY = 0.47
SHIFTS = 14
INTERVAL_S = (4.0, 600.0)
INTERVAL_MEDIAN_S, INTERVAL_SIGMA = 250.0, 0.85
SPEED_KMH, SPEED_BETA = (8.0, 60.0), (1.5, 2.5)  # 27.5 km/h at the mean
STOP_S, STOP_BETA = (60.0, 5400.0), (1.2, 2.0)  # 34 minutes at the mean
GPS_SIGMA_M = 8.0
JUNK_ONE_IN = 2000
MIN_FLEET = 512
HEAVY = 64  # the taxis with the most rows, ``context()["heavy"]``
M_PER_DEG = 111_320.0
WEEK_S = 7 * 86_400
HALF_DAY_S = 43_200


class City:
    """The street grid's crossings that are hot spots, heaviest first."""

    def __init__(self, rng):
        urban = rng.random(N_HOT) < URBAN_SHARE
        x = np.where(urban, rng.normal(CENTRE[0], URBAN_SIGMA[0], N_HOT),
                     rng.uniform(OUTER[0], OUTER[2], N_HOT))
        y = np.where(urban, rng.normal(CENTRE[1], URBAN_SIGMA[1], N_HOT),
                     rng.uniform(OUTER[1], OUTER[3], N_HOT))
        x = np.clip(x, OUTER[0] + MARGIN_DEG, OUTER[2] - MARGIN_DEG)
        y = np.clip(y, OUTER[1] + MARGIN_DEG, OUTER[3] - MARGIN_DEG)
        self.x = np.round(x / PITCH_DEG[0]) * PITCH_DEG[0]
        self.y = np.round(y / PITCH_DEG[1]) * PITCH_DEG[1]
        order = rng.permutation(N_HOT)  # heaviest first, wherever they lie
        self.x, self.y = self.x[order], self.y[order]
        w = np.arange(1, N_HOT + 1, dtype=np.float64) ** -HOT_ZIPF
        self.w = w / w.sum()


def fleet_size(config: dict, n: int) -> int:
    """The configuration's taxis at its own size; proportionally fewer
    under it, never under ``MIN_FLEET`` (nor over the configuration's)."""
    taxis, rows = int(config["data"]["taxis"]), int(config["rows"])
    if n >= rows:
        return taxis
    return min(taxis, max(MIN_FLEET, int(taxis * n / rows + 0.5)))


def fleet_key(owner, seconds):
    """One ascending key for the whole fleet: a taxi, then a second of its
    duty clock (under a week), so one ``searchsorted`` serves every taxi."""
    return owner * float(WEEK_S) + seconds


def deal_rows(weights, n: int) -> np.ndarray:
    """``n`` rows dealt by ``weights``, to the row, at least two each."""
    w = np.cumsum(np.asarray(weights, np.float64))
    spare = n - 2 * len(w)
    if spare < 0:
        raise ValueError(f"{n} rows are under two a taxi for {len(w)} taxis")
    ends = np.round(w / w[-1] * spare).astype(np.int64)
    return np.diff(ends, prepend=0) + 2


class Columns:
    """The generator's columns (the module's docstring names them)."""

    def __init__(self, config: dict, n: int, seed: int):
        self.schema, self.dtg, self.geom = parse_schema(config["schema"])
        names = [a for a, _ in self.schema if a not in (self.dtg, self.geom)]
        if names != ["taxiId"]:
            raise KeyError(f"datagen/tdrive.py makes taxiId, a date and a point, not {names}")
        self.t0 = int(np.datetime64(config["data"]["t0"], "ms").astype(np.int64))
        if int(config["span_days"]) * 86_400 != WEEK_S:
            raise ValueError("datagen/tdrive.py lays a week of shifts: span_days is 7")
        self.span_ms = WEEK_S * 1000
        self.city = City(np.random.default_rng([int(config["data"]["city_seed"])]))
        rng = np.random.default_rng([int(seed), 0])
        fleet = self.fleet = fleet_size(config, n)

        # -- every taxi's duty, interval and rows
        duty_s = WEEK_S * DUTY * rng.uniform(0.9, 1.1, fleet)
        interval = np.clip(INTERVAL_MEDIAN_S * np.exp(rng.normal(0.0, INTERVAL_SIGMA, fleet)),
                           *INTERVAL_S)
        self.rows = deal_rows(duty_s / interval, n)
        self.first = np.concatenate([[0], np.cumsum(self.rows)[:-1]])
        self.taxi = np.repeat(np.arange(1, fleet + 1, dtype=np.int32), self.rows)
        v = self.taxi - 1  # each row's place in the fleet's arrays
        step_s = duty_s / self.rows  # the interval the deal left (whole rows)

        # -- shifts: one in each half day, SHIFTS of them, duty_s in all
        part = rng.uniform(0.5, 1.5, (fleet, SHIFTS))
        length = np.minimum(part / part.sum(1, keepdims=True) * duty_s[:, None],
                            HALF_DAY_S - 60.0)
        begin = (np.arange(SHIFTS) * HALF_DAY_S
                 + rng.uniform(0.0, 1.0, (fleet, SHIFTS)) * (HALF_DAY_S - length))
        done = np.cumsum(length, 1)  # duty seconds served when a shift ends
        # -- a report's second on the duty clock, then on the wall's
        k = np.arange(n) - self.first[v]
        tau = (k + rng.uniform(0.3, 0.7, fleet)[v] + rng.uniform(-0.3, 0.3, n)) * step_s[v]
        tau = np.minimum(tau, done[v, -1] - 1e-3)
        shift = np.searchsorted(fleet_key(np.arange(fleet)[:, None], done).ravel(),
                                fleet_key(v, tau), "right")
        shift = np.minimum(shift, v * SHIFTS + SHIFTS - 1)  # the flat place of (taxi, shift)
        served = np.concatenate([[0.0], done.ravel()[:-1]])
        served[::SHIFTS] = 0.0  # duty seconds served when a shift begins
        wall = np.floor(begin.ravel()[shift] + (tau - served[shift])).astype(np.int64)
        self._distinct_seconds(wall)
        self.t = self.t0 + wall * 1000

        # -- trips on the duty clock, and where a taxi is at each report
        self.x, self.y = self._places(rng, duty_s, v, tau)
        per_x = 1.0 / (M_PER_DEG * np.cos(np.radians(self.y)))
        self.x += rng.normal(0.0, GPS_SIGMA_M, n) * per_x
        self.y += rng.normal(0.0, GPS_SIGMA_M, n) / M_PER_DEG
        junk = rng.choice(n, int(round(n / JUNK_ONE_IN)), replace=False)
        self.x[junk] = rng.uniform(-180, 180, len(junk))
        self.y[junk] = rng.uniform(-80, 80, len(junk))
        self.junk = np.sort(junk)
        self.attrs = {"taxiId": np.repeat(np.arange(1, fleet + 1).astype("<U5"), self.rows)}

    def _distinct_seconds(self, wall) -> None:
        """No two reports of a taxi in one second: a report that did not
        pass its predecessor moves to the second after it (a thin tail:
        intervals are four seconds and more)."""
        for _ in range(8):
            stuck = np.flatnonzero((wall[1:] <= wall[:-1]) & (self.taxi[1:] == self.taxi[:-1])) + 1
            if not len(stuck):
                return
            wall[stuck] = wall[stuck - 1] + 1
        raise RuntimeError("a taxi's reports could not be told apart by the second")

    def _places(self, rng, duty_s, v, tau):
        """(x, y) of every report: its taxi's place ``tau`` seconds into
        its duty, along its chain of trips."""
        city, fleet = self.city, self.fleet
        # more trips than a taxi's duty can hold (a trip and its stop take
        # half an hour at the mean; a chain that ends early stands still)
        per = (np.ceil(duty_s / 600.0) + 8).astype(np.int64)
        starts = np.concatenate([[0], np.cumsum(per)[:-1]])
        m = int(per.sum())
        owner = np.repeat(np.arange(fleet), per)
        to = rng.choice(N_HOT, m, p=city.w)
        frm = np.concatenate([[0], to[:-1]])
        frm[starts] = rng.choice(N_HOT, fleet, p=city.w)
        ax, ay, bx, by = city.x[frm], city.y[frm], city.x[to], city.y[to]
        lon_m = M_PER_DEG * np.cos(np.radians(CENTRE[1]))
        dx, dy = np.abs(bx - ax) * lon_m, np.abs(by - ay) * M_PER_DEG
        speed = (SPEED_KMH[0] + (SPEED_KMH[1] - SPEED_KMH[0]) * rng.beta(*SPEED_BETA, m)) / 3.6
        drive = (dx + dy) / speed
        stop = STOP_S[0] + (STOP_S[1] - STOP_S[0]) * rng.beta(*STOP_BETA, m)
        ends = seg_cumsum(drive + stop, starts, per)
        # a report's trip: the first of its taxi's that ends after tau
        trip = np.searchsorted(fleet_key(owner, np.minimum(ends, WEEK_S - 1.0)),
                               fleet_key(v, tau), "right")
        trip = np.minimum(trip, (starts + per - 1)[v])
        gone = np.minimum((tau - (ends - drive - stop)[trip]) * speed[trip],
                          (dx + dy)[trip])  # metres driven; the stop adds none
        x_first = rng.integers(0, 2, m, dtype=np.int8)[trip].astype(bool)
        lead = np.where(x_first, dx[trip], dy[trip])
        on_lead, on_rest = np.minimum(gone, lead), np.maximum(gone - lead, 0.0)
        mx = np.where(x_first, on_lead, on_rest)
        my = np.where(x_first, on_rest, on_lead)
        x = ax[trip] + np.sign(bx - ax)[trip] * mx / lon_m
        y = ay[trip] + np.sign(by - ay)[trip] * my / M_PER_DEG
        return x, y

    # --------------------------------------------------------------------- surface
    def __len__(self) -> int:
        return len(self.x)

    def context(self) -> dict:
        """What a request generator may know of the data: the hot spots
        (``cx`` / ``cy`` / ``w``, heaviest first, as the other data sets
        name their centres), the fleet's size (ids are ``"1"`` ..
        ``str(fleet)``) and the ``HEAVY`` taxi numbers with the most rows,
        heaviest first."""
        heavy = np.argsort(-self.rows, kind="stable")[:HEAVY] + 1
        return {"cx": [float(v) for v in self.city.x], "cy": [float(v) for v in self.city.y],
                "w": [float(v) for v in self.city.w], "fleet": int(self.fleet),
                "heavy": [int(v) for v in heavy],
                "t0": self.t0, "span_ms": self.span_ms, "n_rows": len(self)}

    def row(self, i: int) -> dict:
        """Row i as a witness row is brought to."""
        return {self.dtg: int(self.t[i]), self.geom: [float(self.x[i]), float(self.y[i])],
                "taxiId": self.attrs["taxiId"][i].item()}


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
