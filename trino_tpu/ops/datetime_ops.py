"""Vectorized civil-calendar math on epoch-day arrays.

Reference: Trino's date/time scalar functions
(``core/trino-main/.../operator/scalar/DateTimeFunctions.java``) delegate to
java.time; on TPU we need branch-free integer arithmetic. Uses the
days<->civil algorithms from Howard Hinnant's public-domain date algorithms
(the same math java.time uses), fully vectorizable on the VPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from trino_tpu.ops import expr_key


def civil_from_days(days):
    """epoch days -> (year, month, day), elementwise (int32 arrays)."""
    expr_key.eager_only("civil_from_days")
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097  # [0, 146096]
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365  # [0, 399]
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)  # [0, 365]
    mp = (5 * doy + 2) // 153  # [0, 11]
    d = doy - (153 * mp + 2) // 5 + 1  # [1, 31]
    m = mp + jnp.where(mp < 10, 3, -9)  # [1, 12]
    y = y + (m <= 2)
    return y.astype(jnp.int64), m.astype(jnp.int64), d.astype(jnp.int64)


def days_from_civil(y, m, d):
    """(year, month, day) -> epoch days, elementwise."""
    expr_key.eager_only("days_from_civil")
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400  # [0, 399]
    mp = m + jnp.where(m > 2, -3, 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def extract_year(days):
    return civil_from_days(days)[0]


def extract_month(days):
    return civil_from_days(days)[1]


def extract_day(days):
    return civil_from_days(days)[2]


def extract_quarter(days):
    return (civil_from_days(days)[1] - 1) // 3 + 1


def add_months(days, n):
    """date + INTERVAL n MONTH with end-of-month clamping (SQL semantics)."""
    y, m, d = civil_from_days(days)
    m0 = m - 1 + n
    y2 = y + jnp.floor_divide(m0, 12)
    m2 = jnp.mod(m0, 12) + 1
    d2 = jnp.minimum(d, days_in_month(y2, m2))
    return days_from_civil(y2, m2, d2)


def extract_dow(days):
    """ISO day-of-week, Monday=1..Sunday=7 (reference:
    DateTimeFunctions.dayOfWeekFromDate). 1970-01-01 was a Thursday."""
    return jnp.mod(days.astype(jnp.int64) + 3, 7) + 1


def extract_doy(days):
    """Day of year, 1-based."""
    y, _, _ = civil_from_days(days)
    jan1 = days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return days.astype(jnp.int64) - jan1 + 1


def extract_week(days):
    """ISO-8601 week number (reference: DateTimeFunctions.weekFromDate):
    week 1 contains the year's first Thursday."""
    d = days.astype(jnp.int64)
    thursday = d - extract_dow(d) + 4  # Thursday of this ISO week
    y, _, _ = civil_from_days(thursday)
    jan1 = days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return (thursday - jan1) // 7 + 1


def trunc_date(days, unit: str):
    """date_trunc(unit, date) -> epoch days (reference:
    DateTimeFunctions.truncateDate)."""
    d = days.astype(jnp.int64)
    if unit == "day":
        return d
    if unit == "week":  # ISO week start (Monday)
        return d - (extract_dow(d) - 1)
    y, m, _dd = civil_from_days(d)
    one = jnp.ones_like(y)
    if unit == "month":
        return days_from_civil(y, m, one)
    if unit == "quarter":
        return days_from_civil(y, ((m - 1) // 3) * 3 + 1, one)
    if unit == "year":
        return days_from_civil(y, one, one)
    raise NotImplementedError(f"date_trunc unit: {unit}")


def days_in_month(y, m):
    lengths = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31], dtype=jnp.int64)
    base = lengths[m - 1]
    leap = ((jnp.mod(y, 4) == 0) & (jnp.mod(y, 100) != 0)) | (jnp.mod(y, 400) == 0)
    return base + ((m == 2) & leap)
