"""Closed loop: ``sessions`` clients, each sending its next request when its
last one ends, ``per_session`` requests each.  With ``first_budget:
uniform`` a session's first request has only a uniform share of its output
budget left, so that completions are spread from the start."""


def schedule(arr: dict, rng, seconds: float) -> dict:
    s = arr["sessions"]
    return {"due": None, "sessions": s, "count": s * arr["per_session"],
            "partial": s if arr.get("first_budget") == "uniform" else 0}
