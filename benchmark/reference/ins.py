"""``ins``: append one calendar day (DAY) of orders; the answer is the
number of rows written."""

from reference.common import days
from reference.sel import per_day


def answer(data, params, state=None):
    d0, counts, _totals = per_day(data)
    return [[int(counts[days(params["DAY"]) - d0].sum())]]
