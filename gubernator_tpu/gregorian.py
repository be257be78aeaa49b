"""Calendar-period expiry for DURATION_IS_GREGORIAN.

Host-side only: the device compares integer millisecond timestamps, the
host does calendars (SURVEY.md §7.3) — here, and in the fused wire
ingest's C++ twin of ``gregorian_expiration`` (``ops/_native.cpp ›
Period``, which ``pack_wire_wave`` asks a calendar row's period end of;
``tests/test_native_calendar.py`` holds it to this module ms for ms,
and this module stays the rule and the classic lane's calendar).
Mirrors the behavior of the
reference's holster gregorian helpers (algorithms.go › tokenBucket's
GregorianExpiration call — reconstructed): the bucket expires at the END
of the current calendar period in UTC, so every key resets at the period
boundary.

THE RULE, stated here once (``types.py`` beside ``created_at`` points
here; ``core/batch.py › pack_requests`` / ``› pack_columns`` and
``oracle.py › Oracle.check`` apply it): **a request's calendar period
is the one that holds the clock the request is APPLIED at** — its
``created_at`` stamp where it carries one, the serving daemon's clock
where it carries none.  ``now`` and ``greg_end`` of one row are never
taken from two clocks.  Upstream reads ``clock.Now()`` for both, so the
two agree there by construction; here a forwarded request applies at its
caller's stamp (``types.RateLimitRequest.created_at``), and a period end
taken from the wall clock would open a bucket at 23:59:59.9 on the stamp
that expires with TOMORROW's period on the wall clock — or, for a caller
whose clock runs ahead, one that is born expired and never denies.

MINUTES, HOURS, DAYS and WEEKS are integer divisions of epoch-ms (the
epoch began on a Thursday at 00:00 UTC, weeks start on Monday); MONTHS
and YEARS keep the calendar.
"""
from __future__ import annotations

import calendar
import datetime as _dt

from .types import GREGORIAN_APPROX_MS, GregorianDuration

_UTC = _dt.timezone.utc
_DAY_MS = 86_400_000
#: period length in ms of the ordinals whose periods all have one length
_FIXED_MS = {
    int(GregorianDuration.MINUTES): 60_000,
    int(GregorianDuration.HOURS): 3_600_000,
    int(GregorianDuration.DAYS): _DAY_MS,
    int(GregorianDuration.WEEKS): 7 * _DAY_MS,
}
#: 1970-01-01 was a Thursday: the Monday before it lies 3 days back
_WEEK_SHIFT_MS = 3 * _DAY_MS


def gregorian_expiration(now_ms: int, ordinal: int) -> int:
    """Epoch-ms of the end of the calendar period containing ``now_ms``
    — the clock the request is applied at (the module's rule).

    ``ordinal`` is a GregorianDuration value.  Raises ValueError on an
    unknown ordinal (the reference surfaces this as a per-request error).
    """
    d = int(GregorianDuration(ordinal))  # raises ValueError if out of range
    now_ms = int(now_ms)
    width = _FIXED_MS.get(d)
    if width is not None:
        shift = _WEEK_SHIFT_MS if d == GregorianDuration.WEEKS else 0
        return (now_ms + shift) // width * width + width - shift
    now = _dt.datetime.fromtimestamp(now_ms // 1000, tz=_UTC)
    if d == GregorianDuration.MONTHS:
        year, month = ((now.year, now.month + 1) if now.month < 12
                       else (now.year + 1, 1))
    else:  # YEARS
        year, month = now.year + 1, 1
    return calendar.timegm((year, month, 1, 0, 0, 0)) * 1000


def gregorian_rate_duration_ms(ordinal: int) -> int:
    """Fixed-width ms used for leak-rate math when a Gregorian ordinal is
    given (actual expiry still follows the calendar)."""
    return GREGORIAN_APPROX_MS[GregorianDuration(ordinal)]
