"""Open loop: ``rate_per_s × seconds`` arrivals in the window, at times
uniform over it (a Poisson process given its count), each request due
whether or not earlier ones have finished.  ``in_flight`` more requests are
already being served when the window opens (admitted in set-up, with a
uniform share of their budget left), so that the window sees the pool in
its steady state rather than filling from empty."""
import numpy as np


def schedule(arr: dict, rng, seconds: float) -> dict:
    head = arr.get("in_flight", 0)
    n = int(round(arr["rate_per_s"] * seconds))
    due = np.concatenate([np.zeros(head),
                          np.sort(rng.uniform(0.0, seconds, n))])
    return {"due": due, "head": head}
