"""The proleptic Gregorian calendar in integer arithmetic, for whole
columns at once: day number (days since 1970-01-01) <-> year, month, day.
H. Hinnant's ``days_from_civil`` / ``civil_from_days`` written for numpy
int64 arrays; exact, no table, no datetime object a row."""

from __future__ import annotations

import numpy as np


def days_from_civil(y, m, d):
    y = np.asarray(y, dtype=np.int64) - (np.asarray(m) <= 2)
    era = y // 400
    yoe = y - era * 400
    doy = (153 * (m + np.where(np.asarray(m) > 2, -3, 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def civil_from_days(z):
    z = np.asarray(z, dtype=np.int64) + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + np.where(mp < 10, 3, -9)
    return yoe + era * 400 + (m <= 2), m, d


def split_ymd(ymd):
    """yyyymmdd -> (year, month, day)."""
    ymd = np.asarray(ymd, dtype=np.int64)
    return ymd // 10000, ymd // 100 % 100, ymd % 100


def days_from_ymd(ymd):
    return days_from_civil(*split_ymd(ymd))


def ymd_from_days(z):
    y, m, d = civil_from_days(z)
    return y * 10000 + m * 100 + d
