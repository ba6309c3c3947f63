"""AIS position reports shaped like MarineCadastre.gov's "Vessel Traffic
Data" (BOEM / NOAA: US Coast Guard AIS thinned to one report a minute a
vessel), for upstream's ``marinecadastre-ais-csv`` type as far as it is
recalled (the configuration's ``about.assumed`` lists what is a guess).
All from a seed; nothing is read from the published files (no network).

**The coast.** One coast, lon -126..-114, lat 30..50 (UTM zones 10-11): a
piecewise-linear shoreline (``COAST``) and, drawn from the configuration's
``data.coast_seed`` and NOT from the run's seed (the ports and lanes are the
deployment's; with them drawn anew a run, a tube's cost followed the seed's
geography: 245-299 ms at the median, PERF.md section 6), ``N_PORTS`` ports on
it at uniform latitudes with Zipf(``PORT_ZIPF``) weights by a drawn rank, ``N_LANES``
lanes, each a polyline of 3 to 6 waypoints from one port to another at
least ``LANE_MIN_KM`` away (both drawn by the ports' weights), its inner
waypoints 20-80 km offshore.

**The rows**, one a position report; row i has feature id i. Rows come
vessel by vessel, a vessel's in time order (MarineCadastre's files are no
more sorted than that), so a voyage is a run of consecutive rows:

- *lane vessels* transmit once a minute through the whole span, each with
  its own phase and a few seconds of jitter, times on whole seconds as
  ``BaseDateTime`` has them. A vessel's time line alternates a **dwell**
  (``moored``: a berth N(port, ``BERTH_SIGMA_DEG``) on each axis, every
  report the berth plus N(0, ``GPS_SIGMA_M``), 6-72 h) and a **voyage**
  (``way``: along a lane of the port it lies in, from its berth to a berth
  in the lane's other port, at one speed of 8-22 kn, the lane's inner
  waypoints moved by one N(0, ``CROSS_SIGMA_M``) offset a voyage): every
  voyage begins and ends with that vessel's dwell in the two ports. The
  time line starts before the span in a seeded state, so that the span's
  first minute already holds vessels under way. Dwell lengths are 6 + 66 x
  Beta hours with the mean that makes the two classes' rows 55 : 35 for
  this seed's lanes;
- *coastal vessels* (fishing, tugs, pleasure craft) appear for one trip
  each: a random walk at 2-8 kn from near a port (drawn by weight), a
  report a minute, kept within ``COASTAL_KM`` of where it began;
- *mislocated fixes*: uniform over lon -180..180, lat -80..80, each given
  to some vessel at some time of the span (real feeds hold them; they
  stretch the statistics' envelope and lie in no answer).

Shares of the rows (``SHARES``): coastal 9.5% and mislocated 0.5% exactly,
moored and under way what the time lines give (55% and 35% to within a
point or two at 2^23 rows; tests/test_ais_cell.py). Coordinates are f64
and free: no two reports share both. **Density is the deployment's at every size**:
``make(config, n, seed)`` with fewer rows than the configuration's covers
a shorter span with the same vessels a minute (never under ``MIN_MINUTES``,
below which the fleet thins instead: rehearsals and tests), and
``Columns.span_ms`` says which.

The other attributes are synthetic with the columns' shapes: a vessel's
own (``mmsi``, ``vessel_name``, ``imo``, ``call_sign``, ``vessel_type``,
``length``, ``width``, ``draft``, ``cargo``) repeat on its every report;
``sog``, ``cog``, ``heading`` and ``status`` follow what it was doing.

``Columns.context()`` gives the request generators the ports (heaviest
first), the lanes, every voyage that begins inside the span (its vessel,
its first row, how many of the vessel's rows follow) and the reports'
own ``x``, ``y``, ``t``: a tube's track is a vessel's own reports.
"""

from __future__ import annotations

import numpy as np

from datagen.gdelt import parse_schema

REGION = (-126.0, 30.0, -114.0, 50.0)  # lon0, lat0, lon1, lat1
#: the shoreline: (latitude, longitude), south to north
COAST = ((30.0, -115.9), (32.6, -117.2), (34.0, -119.4), (34.5, -120.5), (36.6, -121.9),
         (37.8, -122.5), (40.4, -124.4), (43.0, -124.4), (46.2, -124.0), (48.4, -124.7),
         (50.0, -127.5))
N_PORTS, N_LANES, PORT_ZIPF = 48, 24, 1.1
PORT_LAT = (32.6, 48.4)
LANE_MIN_KM = 150.0
SHARES = {"moored": 0.55, "way": 0.35, "coastal": 0.095, "junk": 0.005}
CLASSES = tuple(SHARES)  # ``Columns.kind`` holds a row's place in this tuple
MOORED, WAY, COASTAL, JUNK = range(len(CLASSES))
REPORT_MS = 60_000
MIN_MINUTES = 2 * 1440
BERTH_SIGMA_DEG = 0.01
GPS_SIGMA_M = 15.0
CROSS_SIGMA_M = 600.0
COASTAL_KM = 30.0
M_PER_DEG = 111_320.0
KNOT_MS = 1852.0 / 3600.0  # metres a second
ATTRIBUTES = ("mmsi", "sog", "cog", "heading", "vessel_name", "imo", "call_sign",
              "vessel_type", "status", "length", "width", "draft", "cargo")
STATUS = ("moored", "at anchor", "under way using engine", "engaged in fishing", "undefined")
_WORDS_A = ("PACIFIC", "OCEAN", "NORTHERN", "GOLDEN", "SEA", "CAPE", "STAR", "POLAR", "EVER",
            "MAERSK", "MSC", "COSCO", "ALASKAN", "WESTERN", "LADY", "MISS", "BLUE", "SILVER")
_WORDS_B = ("STAR", "SPIRIT", "TRADER", "PIONEER", "EXPLORER", "HARMONY", "GLORY", "EAGLE",
            "WAVE", "DAWN", "VOYAGER", "RANGER", "PRIDE", "QUEEN", "HAWK", "MARINER", "BAY")


def coast_lon(lat):
    lats, lons = np.array(COAST).T
    return np.interp(lat, lats, lons)


def deg_per_m(lat):
    """(degrees of longitude, of latitude) a metre at ``lat``."""
    return 1.0 / (M_PER_DEG * np.cos(np.radians(lat))), 1.0 / M_PER_DEG


def flat_km(x0, y0, x1, y1):
    """Distance in km on the local plane (the generators' own yardstick:
    "at least 40 km from every port" needs no great circle)."""
    dx = (np.asarray(x1) - x0) * np.cos(np.radians((np.asarray(y1) + y0) / 2)) * M_PER_DEG
    dy = (np.asarray(y1) - y0) * M_PER_DEG
    return np.hypot(dx, dy) / 1e3


def seg_cumsum(a, starts, lengths):
    """Running sums of ``a`` that start again at each segment."""
    c = np.cumsum(a)
    before = np.where(starts > 0, c[np.maximum(starts, 1) - 1], 0.0)
    return c - np.repeat(before, lengths)


class Coast:
    """The ports and lanes ``rng`` draws."""

    def __init__(self, rng):
        lat = np.sort(rng.uniform(*PORT_LAT, N_PORTS))
        lon = coast_lon(lat) + rng.uniform(-0.01, 0.04, N_PORTS)
        rank = rng.permutation(N_PORTS)  # heaviest first, wherever they lie
        self.px, self.py = lon[rank], lat[rank]
        w = np.arange(1, N_PORTS + 1, dtype=np.float64) ** -PORT_ZIPF
        self.pw = w / w.sum()
        self.lanes, self.ends = [], []
        while len(self.lanes) < N_LANES:
            a, b = (int(v) for v in rng.choice(N_PORTS, 2, replace=False, p=self.pw))
            if flat_km(self.px[a], self.py[a], self.px[b], self.py[b]) < LANE_MIN_KM:
                continue
            inner = int(rng.integers(1, 5))
            f = (np.arange(1, inner + 1) + rng.uniform(-0.25, 0.25, inner)) / (inner + 1)
            ylat = self.py[a] + f * (self.py[b] - self.py[a])
            off_km = rng.uniform(20.0, 80.0, inner)
            xlon = coast_lon(ylat) - off_km * 1e3 * deg_per_m(ylat)[0]
            self.lanes.append(np.concatenate([[[self.px[a], self.py[a]]],
                                              np.stack([xlon, ylat], 1),
                                              [[self.px[b], self.py[b]]]]))
            self.ends.append((a, b))
        self.at_port = {}  # port -> [(lane, leaves from its first end?)]
        for k, (a, b) in enumerate(self.ends):
            self.at_port.setdefault(a, []).append((k, True))
            self.at_port.setdefault(b, []).append((k, False))

    def lane_hours(self) -> float:
        """A voyage's mean length in hours, over lanes taken equally often
        (a walk that leaves a port by one of its lanes at random takes
        every lane equally often) and speeds uniform over 8-22 kn."""
        km = np.array([flat_km(p[:-1, 0], p[:-1, 1], p[1:, 0], p[1:, 1]).sum()
                       for p in self.lanes])
        return float(np.mean(km) / 1.852 * np.log(22.0 / 8.0) / 14.0)


class Columns:
    """The generator's columns (the module's docstring names them), and
    ``kind``: each row's class as its place in ``CLASSES``."""

    def __init__(self, config: dict, n: int, seed: int):
        self.schema, self.dtg, self.geom = parse_schema(config["schema"])
        names = [a for a, _ in self.schema if a not in (self.dtg, self.geom)]
        if sorted(names) != sorted(ATTRIBUTES):
            raise KeyError(f"datagen/ais.py makes {ATTRIBUTES}, not {names}")
        self.t0 = int(np.datetime64(config["data"]["t0"], "ms").astype(np.int64))
        whole = int(config["span_days"]) * 1440
        minutes = self.minutes = int(min(whole, max(MIN_MINUTES,
                                                    round(whole * n / int(config["rows"])))))
        self.span_ms = minutes * REPORT_MS
        rng = np.random.default_rng([int(seed), 0])
        # the coast is the deployment's: every seed sails the same lanes between the same ports
        self.coast = Coast(np.random.default_rng([int(config["data"]["coast_seed"])]))
        n_junk = int(round(SHARES["junk"] * n))
        n_coastal = int(round(SHARES["coastal"] * n))
        n_lane = n - n_junk - n_coastal
        n_liners = -(-n_lane // minutes)  # the last one appears late, with what is left
        fleet = max(int(round(int(config["data"]["vessels"]) * n / int(config["rows"]))),
                    n_liners + 16)
        n_craft = fleet - n_liners

        self.x, self.y = np.empty(n), np.empty(n)
        self.t = np.empty(n, np.int64)
        self.kind = np.empty(n, np.int8)
        self.vessel = np.empty(n, np.int32)
        sog, cog = np.zeros(n), np.zeros(n)
        heading = np.full(n, 511, np.int32)
        voyages = self._liners(rng, n_lane, n_liners, sog, cog, heading)
        self._craft(rng, n_lane, n_coastal, n_liners, n_craft, sog, cog)
        lo = n_lane + n_coastal  # the mislocated fixes
        self.x[lo:], self.y[lo:] = rng.uniform(-180, 180, n_junk), rng.uniform(-80, 80, n_junk)
        self.t[lo:] = self.t0 + rng.integers(0, self.span_ms // 1000, n_junk) * 1000
        self.vessel[lo:] = rng.integers(0, fleet, n_junk)
        self.kind[lo:] = JUNK
        sog[lo:], cog[lo:] = rng.uniform(0, 20, n_junk), rng.uniform(0, 360, n_junk)
        self.voyages = voyages
        self.attrs = self._attributes(rng, names, fleet, n_liners, sog, cog, heading)

    # ---------------------------------------------------------------- lane vessels
    def _liners(self, rng, n_lane, n_liners, sog, cog, heading) -> dict:
        """Rows [0, n_lane): every lane vessel's time line, a report a minute."""
        coast, minutes = self.coast, self.minutes
        voyage_h = coast.lane_hours()
        mean = np.clip((voyage_h * SHARES["moored"] / SHARES["way"] - 6.0) / 66.0, 0.05, 0.9)
        beta = (1.5, 1.5 * (1.0 - mean) / mean)
        p_way = SHARES["way"] / (SHARES["way"] + SHARES["moored"])
        vx = self.x[:n_lane]  # base positions first, the fixes' noise at the end
        vy = self.y[:n_lane]
        kind = self.kind[:n_lane]
        out = {k: [] for k in ("vessel", "row", "rows_left", "lane", "port")}
        row = 0
        for v in range(n_liners):
            m_v = min(minutes, n_lane - row)  # the last vessel appears late
            first = minutes - m_v
            phase = int(rng.integers(0, 60)) * 1000
            jitter = rng.integers(-5, 6, m_v) * 1000
            self.t[row:row + m_v] = (self.t0 + phase + jitter
                                     + (first + np.arange(m_v, dtype=np.int64)) * REPORT_MS)
            np.clip(self.t[row:row + m_v], self.t0, self.t0 + self.span_ms - 1000,
                    out=self.t[row:row + m_v])
            self.vessel[row:row + m_v] = v
            port = coast.ends[int(rng.integers(0, N_LANES))][int(rng.integers(0, 2))]
            berth = self._berth(rng, port)
            at = 0  # minutes of this vessel's time line laid so far
            under_way = rng.random() < p_way
            lead = rng.random()  # the share of the first leg that lies before the span
            while at < m_v:
                if under_way:
                    opts = coast.at_port[port]
                    lane, fwd = opts[int(rng.integers(0, len(opts)))]
                    to = coast.ends[lane][1 if fwd else 0]
                    dest = self._berth(rng, to)
                    path = coast.lanes[lane] if fwd else coast.lanes[lane][::-1]
                    path = path.copy()
                    shift = rng.normal(0.0, CROSS_SIGMA_M, 2) * deg_per_m(path[1:-1, 1].mean())
                    path[1:-1] += shift
                    path[0], path[-1] = berth, dest
                    knots = float(rng.uniform(8.0, 22.0))
                    seg_m = flat_km(path[:-1, 0], path[:-1, 1], path[1:, 0], path[1:, 1]) * 1e3
                    cum = np.concatenate([[0.0], np.cumsum(seg_m)])
                    total = int(np.ceil(cum[-1] / (knots * KNOT_MS * 60.0)))
                    skip = int(lead * total) if at == 0 else 0
                    k = min(total - skip, m_v - at)
                    s = (skip + np.arange(k)) * (knots * KNOT_MS * 60.0)
                    a, b = row + at, row + at + k
                    vx[a:b] = np.interp(s, cum, path[:, 0])
                    vy[a:b] = np.interp(s, cum, path[:, 1])
                    leg = np.clip(np.searchsorted(cum, s, "right") - 1, 0, len(seg_m) - 1)
                    dx = (path[leg + 1, 0] - path[leg, 0]) * np.cos(np.radians(vy[a:b]))
                    course = np.degrees(np.arctan2(dx, path[leg + 1, 1] - path[leg, 1])) % 360.0
                    kind[a:b] = WAY
                    sog[a:b], cog[a:b] = knots, course
                    heading[a:b] = np.round(course).astype(np.int32) % 360
                    if skip == 0 and at + first > 0:  # it left its berth inside the span
                        out["vessel"].append(v)
                        out["row"].append(a)
                        out["rows_left"].append(row + m_v - a)
                        out["lane"].append(lane)
                        out["port"].append(port)
                    port, berth = to, dest
                else:
                    total = int((6.0 + 66.0 * rng.beta(*beta)) * 60)
                    skip = int(lead * total) if at == 0 else 0
                    k = min(total - skip, m_v - at)
                    a, b = row + at, row + at + k
                    vx[a:b], vy[a:b] = berth
                    kind[a:b] = MOORED
                    heading[a:b] = int(rng.integers(0, 360))
                at += k
                under_way = not under_way
            row += m_v
        self._fix_noise(rng, 0, n_lane)
        moored = kind == MOORED
        sog[:n_lane] = np.where(moored, np.abs(rng.normal(0, 0.1, n_lane)),
                                sog[:n_lane] + rng.normal(0, 0.3, n_lane))
        cog[:n_lane] = np.where(moored, rng.uniform(0, 360, n_lane),
                                (cog[:n_lane] + rng.normal(0, 1.5, n_lane)) % 360.0)
        return {k: np.asarray(v, np.int64) for k, v in out.items()}

    def _berth(self, rng, port: int):
        return (self.coast.px[port] + rng.normal(0.0, BERTH_SIGMA_DEG),
                self.coast.py[port] + rng.normal(0.0, BERTH_SIGMA_DEG))

    def _fix_noise(self, rng, lo: int, hi: int) -> None:
        """Every fix its own N(0, GPS_SIGMA_M) on each axis."""
        per_x, per_y = deg_per_m(self.y[lo:hi])
        self.x[lo:hi] += rng.normal(0.0, GPS_SIGMA_M, hi - lo) * per_x
        self.y[lo:hi] += rng.normal(0.0, GPS_SIGMA_M, hi - lo) * per_y

    # ------------------------------------------------------------- coastal vessels
    def _craft(self, rng, lo, n_rows, first_vessel, n_craft, sog, cog) -> None:
        """Rows [lo, lo + n_rows): one trip a coastal vessel, a random walk."""
        if n_rows == 0:
            return
        n_craft = min(n_craft, n_rows)
        w = np.cumsum(rng.uniform(0.5, 1.5, n_craft))
        ends = np.round(w / w[-1] * n_rows).astype(np.int64)  # the trips' lengths sum to n_rows
        lengths = np.diff(ends, prepend=0)
        starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
        trip = np.repeat(np.arange(n_craft), lengths)
        port = rng.choice(N_PORTS, n_craft, p=self.coast.pw)
        knots = rng.uniform(2.0, 8.0, n_craft)
        turn = rng.uniform(0, 2 * np.pi, n_craft)[trip] + seg_cumsum(
            rng.normal(0.0, 0.2, n_rows), starts, lengths)
        step = (knots * KNOT_MS * 60.0)[trip]
        east = seg_cumsum(step * np.sin(turn), starts, lengths)
        north = seg_cumsum(step * np.cos(turn), starts, lengths)
        far = np.maximum.reduceat(np.hypot(east, north), starts)
        shrink = np.minimum(1.0, (COASTAL_KM - 3.0) * 1e3 / np.maximum(far, 1.0))[trip]
        y0 = self.coast.py[port] + rng.normal(0.0, 1000.0, n_craft) / M_PER_DEG
        x0 = self.coast.px[port] - np.abs(rng.normal(0.0, 1000.0, n_craft)) * deg_per_m(y0)[0]
        hi = lo + n_rows
        self.y[lo:hi] = y0[trip] + north * shrink / M_PER_DEG
        self.x[lo:hi] = x0[trip] + east * shrink * deg_per_m(self.y[lo:hi])[0]
        self._fix_noise(rng, lo, hi)
        begin = rng.integers(0, self.minutes - lengths + 1)
        minute = begin[trip] + (np.arange(n_rows) - starts[trip])
        self.t[lo:hi] = (self.t0 + minute * REPORT_MS + rng.integers(0, 60, n_craft)[trip] * 1000
                         + rng.integers(-5, 6, n_rows) * 1000)
        np.clip(self.t[lo:hi], self.t0, self.t0 + self.span_ms - 1000, out=self.t[lo:hi])
        self.vessel[lo:hi] = first_vessel + trip
        self.kind[lo:hi] = COASTAL
        sog[lo:hi] = np.maximum(knots[trip] * shrink + rng.normal(0, 0.5, n_rows), 0.0)
        cog[lo:hi] = np.degrees(turn) % 360.0

    # ------------------------------------------------------------------ attributes
    def _attributes(self, rng, names, fleet, n_liners, sog, cog, heading) -> dict:
        liner = np.arange(fleet) < n_liners
        kinds = np.where(liner, rng.choice([70, 71, 74, 79, 80, 81, 84, 89, 60], fleet),
                         rng.choice([30, 31, 52, 36, 37], fleet)).astype(np.int32)
        length = np.round(np.where(liner, rng.uniform(90, 366, fleet), rng.uniform(6, 40, fleet)), 1)
        a = np.array(_WORDS_A)[rng.integers(0, len(_WORDS_A), fleet)]
        b = np.array(_WORDS_B)[rng.integers(0, len(_WORDS_B), fleet)]
        name = np.char.add(np.char.add(a, " "), b).astype("<U20")
        digits = rng.integers(1_000_000, 9_999_999, fleet).astype("<U7")
        letters = np.array(list("ABCDEFGHJKLMNPRSTUVWXYZ"))
        sign = np.char.add("W", letters[rng.integers(0, len(letters), (fleet, 2))].view("<U2")
                           .reshape(fleet))
        own = {
            "mmsi": (366_000_000 + rng.permutation(999_999)[:fleet]).astype(np.int32),
            "vessel_name": name,
            "imo": np.where(liner, np.char.add("IMO", digits), "").astype("<U10"),
            "call_sign": np.char.add(sign, rng.integers(1000, 9999, fleet).astype("<U4"))
            .astype("<U7"),
            "vessel_type": kinds,
            "length": length,
            "width": np.round(length / rng.uniform(6.0, 7.5, fleet), 1),
            "draft": np.round(np.where(liner, rng.uniform(6, 15, fleet),
                                       rng.uniform(1, 4, fleet)), 1),
            "cargo": np.where((kinds >= 70) & (kinds < 90), kinds, 0).astype(np.int32),
        }
        n = len(self.x)
        at_anchor = rng.random(n) < 0.2
        code = np.select(
            [self.kind == MOORED, self.kind == WAY,
             (kinds == 30)[self.vessel]], [at_anchor.astype(np.int8), 2, 3], 4)
        per_row = {"sog": np.round(sog, 1), "cog": np.round(cog, 1), "heading": heading}
        out = {}
        for a in names:
            if a in per_row:
                out[a] = per_row[a]
                continue
            # ``take`` into ``out`` with mode "clip" copies rows straight across (the
            # checked modes go through a buffer: twenty times slower for strings)
            src, at = (np.array(STATUS), code) if a == "status" else (own[a], self.vessel)
            out[a] = np.empty(n, src.dtype)
            np.take(src, at, out=out[a], mode="clip")
        return out

    # --------------------------------------------------------------------- surface
    def __len__(self) -> int:
        return len(self.x)

    def context(self) -> dict:
        """What a request generator may know of the data (the module's
        docstring); ``cx`` / ``cy`` are the ports, as the other data sets
        name their centres."""
        c = self.coast
        ports = {"x": [float(v) for v in c.px], "y": [float(v) for v in c.py],
                 "w": [float(v) for v in c.pw], "sigma_deg": BERTH_SIGMA_DEG}
        return {"cx": ports["x"], "cy": ports["y"], "ports": ports,
                "lanes": [p.tolist() for p in c.lanes], "voyages": self.voyages,
                "reports": {"x": self.x, "y": self.y, "t": self.t},
                "t0": self.t0, "span_ms": self.span_ms, "n_rows": len(self)}

    def row(self, i: int) -> dict:
        """Row i as a witness row is brought to: the date as epoch millis,
        the point as [x, y], the rest as Python values."""
        out = {self.dtg: int(self.t[i]), self.geom: [float(self.x[i]), float(self.y[i])]}
        out.update({a: c[i].item() for a, c in self.attrs.items()})
        return out


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
