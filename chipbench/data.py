"""The GHCN-Daily archive of one configuration, drawn from the seed.

``records`` lays out one reading per (station, date, type), as the
archive's daily files do, and draws each value from the seed, uniform
over its type's range in tenths. ``station_facts`` and ``dates`` are
the schema's fixed station table and calendar: the benchmark's own
copy, so the reference shares nothing with the program. ``build``
hands the same records to the program's bulk shredder (the ingest
path ``repro.data.weather.build_database`` takes), so ingest stays the
program's and only the values change with the seed.
"""
from __future__ import annotations

import numpy as np

STATES = ("FLORIDA", "NEW YORK", "WASHINGTON", "CALIFORNIA", "TEXAS",
          "ARIZONA", "OREGON", "NEVADA", "MONTANA", "KANSAS")
# calendar days of each year: the paper's two dates first
MONTH_DAYS = ((12, 25), (7, 4), (1, 15), (3, 10), (5, 20), (8, 30),
              (10, 5), (11, 11))
# element names interned first, in this order, as the program's ingest
# does, so string and name ids do not depend on the seed
NAMES = ("dataCollection", "data", "date", "dataType", "station", "value",
         "stationCollection", "id", "displayName", "latitude", "longitude",
         "locationLabels", "type")


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose (0 data, 1 traffic)."""
    return np.random.default_rng([seed % 2**64, stream])


def dates(cfg: dict) -> list[tuple[int, int, int]]:
    mds = MONTH_DAYS[:cfg["days_per_year"]]
    years = range(cfg["years"][0], cfg["years"][1] + 1)
    return [(y, m, d) for y in years for (m, d) in mds]


def date_str(y: int, m: int, d: int) -> str:
    return f"{y:04d}-{m:02d}-{d:02d}T00:00:00.000"


def station_id(i: int) -> str:
    if i == 0:
        return "GHCND:USW00012836"   # Key West, FLORIDA
    if i == 1:
        return "GHCND:USW00014771"   # Syracuse, NEW YORK
    return f"GHCND:USW9{i:07d}"


def station_facts(cfg: dict) -> dict[str, list]:
    """Per station: id, state, display name, and whether it is in the
    US (every ``foreign_every``-th station is not)."""
    n, k = cfg["num_stations"], cfg["foreign_every"]
    state = [("FLORIDA", "NEW YORK")[i] if i < 2 else STATES[i % len(STATES)]
             for i in range(n)]
    return {"id": [station_id(i) for i in range(n)],
            "state": state,
            "name": [f"STATION {i} {state[i]} AIRPORT" for i in range(n)],
            "is_us": [i < 2 or i % k != k - 1 for i in range(n)]}


def records(cfg: dict, seed: int) -> dict[str, np.ndarray]:
    """One reading per (station, date, type), station-major; values
    uniform over ``value_ranges`` (tenths, [lo, hi)) drawn from the seed."""
    ns, nd, nt = cfg["num_stations"], len(dates(cfg)), len(cfg["datatypes"])
    st, di, ty = (a.ravel() for a in np.meshgrid(
        np.arange(ns), np.arange(nd), np.arange(nt), indexing="ij"))
    lo = np.asarray([cfg["value_ranges"][t][0] for t in cfg["datatypes"]])
    hi = np.asarray([cfg["value_ranges"][t][1] for t in cfg["datatypes"]])
    value = lo[ty] + rng(seed, 0).integers(0, (hi - lo)[ty])
    return {"station": st.astype(np.int32), "date": di.astype(np.int32),
            "dtype": ty.astype(np.int32), "value": value.astype(np.float32)}


def build(cfg: dict, rec: dict[str, np.ndarray]):
    """The program's database of these records: /sensors, its TMIN and
    TMAX subsets, and /stations, each in ``num_partitions`` partitions
    assigned round-robin."""
    from repro.core import xdm
    from repro.data import weather

    spec = weather.WeatherSpec(
        num_stations=cfg["num_stations"],
        years=tuple(range(cfg["years"][0], cfg["years"][1] + 1)),
        days_per_year=cfg["days_per_year"],
        datatypes=tuple(cfg["datatypes"]),
        records_per_doc=cfg["records_per_doc"],
        foreign_every=cfg["foreign_every"])
    db = xdm.Database()
    for name in NAMES:
        db.names.id(name)
    parts = cfg["num_partitions"]
    part_of = np.arange(rec["station"].shape[0]) % parts
    tyname = np.asarray(cfg["datatypes"])[rec["dtype"]]

    def sensor_parts(keep=None):
        return [weather._bulk_sensor_table(
            spec, db, rec, np.nonzero((part_of == p) if keep is None
                                      else (part_of == p) & keep)[0])
            for p in range(parts)]

    db.add_collection("/sensors", sensor_parts())
    db.add_collection("/sensors_min", sensor_parts(tyname == "TMIN"))
    db.add_collection("/sensors_max", sensor_parts(tyname == "TMAX"))
    db.add_collection("/stations", weather._station_tables(spec, db, parts))
    return db
