"""Share of the requests the cell's studies could serve that the window
took, in %: requests sent ÷ ``requests_available`` (what the generator's
studies held when the window opened). A guard, like ``cache_warm_share``: at
100 a client has run out of studies and the run is not correct
(``clients_out_of_studies``) with nothing wrong in the program, so whoever
sizes a gain holds the faster pace against this number first."""


def read(evidence):
    available = evidence.get("requests_available")
    return 100.0 * evidence["attempted"] / available if available else None
