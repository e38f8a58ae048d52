"""Seeded NYC TLC yellow-taxi trip records in the published 2015 layout.

The nineteen columns, their order, names, types and codes are those of
the TLC's 2015 yellow-trip files and data dictionary (VendorID 1-2,
RateCodeID 1-6, payment_type 1 credit, 2 cash, 3 no charge, 4 dispute;
amounts in dollars with cents). The files carry a header line, which is
left out here: the engine's ``read_csv`` takes a declared schema.

Values are drawn column-wise with numpy from ``seed``. Pickups fall on
the 365 days of 2015 uniformly, and on the hours of the day by
``HOUR_SHARE``, a fixed table shaped like the yellow cabs' published
hourly pickup curve: a trough near 5 AM at about a fifth of the evening
peak of 6-7 PM. The table is this benchmark's approximation of that
shape, not counts read from the data. The fare follows the 2015 meter
(2.50 USD initial charge and 0.50 USD a fifth of a mile, with 0.50 USD
for every five minutes of the ride standing in for the minutes in slow
traffic, 52 USD flat from JFK), with the 0.50 USD MTA tax, the 0.30 USD
improvement surcharge and the night and peak extras; tips are recorded
for credit cards only, as in the files; the total is the sum of the
parts to the cent. Drop-off points hold planted hits on two Manhattan
buildings, the paper's Table I query targets.
"""

from __future__ import annotations

import numpy as np

from bench import csvtext as ct

TABLE = "yellow_tripdata_2015.csv"
#: (name, dtype) of each CSV column, in the files' order
SCHEMA = (("VendorID", "int"), ("tpep_pickup_datetime", "str"),
          ("tpep_dropoff_datetime", "str"), ("passenger_count", "int"),
          ("trip_distance", "float"), ("pickup_longitude", "float"),
          ("pickup_latitude", "float"), ("RateCodeID", "int"),
          ("store_and_fwd_flag", "str"), ("dropoff_longitude", "float"),
          ("dropoff_latitude", "float"), ("payment_type", "int"),
          ("fare_amount", "float"), ("extra", "float"), ("mta_tax", "float"),
          ("tip_amount", "float"), ("tolls_amount", "float"),
          ("improvement_surcharge", "float"), ("total_amount", "float"))
#: relative pickups in each hour of the day, 0-23
HOUR_SHARE = (3.9, 2.9, 2.2, 1.6, 1.2, 1.0, 2.1, 3.5, 4.3, 4.4, 4.2, 4.4,
              4.7, 4.7, 4.9, 4.7, 4.1, 4.9, 6.0, 6.3, 5.8, 5.6, 5.5, 4.8)
PASSENGERS = ((1, 2, 3, 4, 5, 6), (0.70, 0.14, 0.04, 0.02, 0.06, 0.04))
RATE_CODES = ((1, 2, 3, 4, 5, 6),
              (0.972, 0.02, 0.002, 0.0005, 0.005, 0.0005))
PAYMENTS = ((1, 2, 3, 4), (0.62, 0.37, 0.007, 0.003))
CREDIT = 1
JFK = 2
# (lon0, lat0, lon1, lat1): the city, and the paper's two query targets
CITY = (-74.03, 40.60, -73.75, 40.90)
GOLDMAN = (-74.0144, 40.7147, -74.0134, 40.7157)  # 200 West St
CITIGROUP = (-74.0122, 40.7197, -74.0112, 40.7207)  # 388 Greenwich St
COORD_DECIMALS = 14


def _coded(rng, table, n):
    codes, p = table
    return np.asarray(codes)[rng.choice(len(codes), n, p=p)]


def generate(n_rows: int, seed: int) -> bytes:
    """``n_rows`` CSV lines, newline-terminated, a pure function of seed."""
    rng = ct.rng_for(seed)
    n = n_rows
    share = np.asarray(HOUR_SHARE) / sum(HOUR_SHARE)
    hour = rng.choice(24, n, p=share)
    pickup = (rng.integers(0, 365, n) * 86400 + hour * 3600
              + rng.integers(0, 3600, n))
    miles_c = np.rint(rng.gamma(1.6, 1.8, n) * 100).astype(np.int64)
    seconds = np.rint(60 + miles_c * 1.8 * rng.gamma(4.0, 0.25, n))
    seconds = seconds.astype(np.int64)
    rate = _coded(rng, RATE_CODES, n)
    pay = _coded(rng, PAYMENTS, n)
    fare = 250 + 50 * ((miles_c * 5) // 100 + seconds // 300)
    fare = np.where(rate == JFK, 5200, fare)
    extra = np.where((hour >= 20) | (hour < 6), 50,
                     np.where(hour >= 16, 100, 0))
    mta, surcharge = 50, 30
    tip = np.where(pay == CREDIT,
                   np.rint(fare * rng.uniform(0.0, 0.3, n)), 0)
    tip = tip.astype(np.int64)
    tolls = np.where(rng.random(n) < 0.05, 554, 0)
    total = fare + extra + mta + tip + tolls + surcharge

    def point():
        return (rng.uniform(CITY[0], CITY[2], n),
                rng.uniform(CITY[1], CITY[3], n))

    p_lon, p_lat = point()
    d_lon, d_lat = point()
    hq = rng.random(n)
    for box, hit in ((GOLDMAN, hq < 0.004),
                     (CITIGROUP, (hq >= 0.004) & (hq < 0.007))):
        d_lon[hit] = rng.uniform(box[0], box[2], hit.sum())
        d_lat[hit] = rng.uniform(box[1], box[3], hit.sum())

    def coord(x):
        return ct.decimal(x, 3, COORD_DECIMALS)

    def cents(c):
        return ct.fixed_point(c, 3, 2)

    return ct.join([
        ct.integer(1 + (rng.random(n) < 0.53), 1),
        ct.timestamps(pickup, "2015-01-01"),
        ct.timestamps(pickup + seconds, "2015-01-01"),
        ct.integer(_coded(rng, PASSENGERS, n), 1),
        cents(miles_c),
        coord(p_lon), coord(p_lat),
        ct.integer(rate, 1),
        ct.choice((rng.random(n) < 0.007).astype(int), ("N", "Y")),
        coord(d_lon), coord(d_lat),
        ct.integer(pay, 1),
        cents(fare), cents(extra), cents(np.full(n, mta)), cents(tip),
        cents(tolls), cents(np.full(n, surcharge)), cents(total),
    ])
