"""TPC-H Q6 (clause 2.4.6) in plain NumPy; parameters DATE (the first
of January of a year), DISC_LO, DISC_HI and QUANTITY."""

import numpy as np

from reference.common import cents, days, dec, isum


def answer(data, params, state=None):
    ship = data.col("lineitem", "l_shipdate")
    qty = data.col("lineitem", "l_quantity")
    price = data.col("lineitem", "l_extendedprice")
    disc = data.col("lineitem", "l_discount")
    lo = np.datetime64(params["DATE"])
    hi = (lo.astype("datetime64[Y]") + 1).astype("datetime64[D]")
    keep = ((ship >= days(str(lo))) & (ship < days(str(hi)))
            & (disc >= cents(params["DISC_LO"]))
            & (disc <= cents(params["DISC_HI"]))
            & (qty < cents(params["QUANTITY"])))
    return [[dec(isum(price[keep] * disc[keep]), 4)]]
